"""repro.obs — observability for the simulator + scheduler stack.

It serves the RailX twin (``core/``, ``arch/``, ``cluster/``): host-side
code whose spans need no device clock.  The JAX path that runs on the
chip (``models/``, ``kernels/``, ``train/``, ``serve/``) traces through
the JAX profiler instead: ``jax.named_scope`` names its device ops
(``embed``, ``layers``, ``attention``, ``mlp``, ``moe``, ``head``,
``kv_cache``, ``optimizer``, ``grad_reduce``), every ``pallas_call``
carries a ``name=``, and ``train_loop``'s host spans (a ``train`` step
annotation with ``train.data``, ``train.sync`` and ``train.checkpoint``)
land on the device trace's clock (``launch/train.py --profile-dir``).

Three layers, all optional and all zero-cost when unused:

* **Tracing** (``tracer``): a :class:`Tracer` emitting structured
  span/instant/counter events in the Chrome trace-event JSON format —
  a dump loads directly in Perfetto (https://ui.perfetto.dev) or
  ``chrome://tracing``.  The default everywhere is the
  :data:`NULL_TRACER` singleton whose methods are allocation-free
  no-ops; instrumented hot paths guard with ``if tracer.enabled:`` so
  disabled tracing costs one branch per site and scheduling stays
  byte-identical either way (asserted by ``tests/test_obs.py``).
* **Metrics** (``metrics``): a :class:`MetricsRegistry` of named
  counters / gauges / histograms with a flat ``snapshot()`` dict.  The
  cluster stack's cache statistics (circuit-shape, goodput, mapping
  solver) live here; the legacy ``.hits``/``.misses`` attributes are
  properties over the registry counters.
* **Validation** (``schema``): :func:`validate_trace` checks the
  structural contract every emitted trace must satisfy (required
  fields, monotonic timestamps, matched B/E spans) — CI runs it on the
  bench-check traces so a broken instrumentation point fails the build.

Worked example — instrument a cluster run, open the trace in Perfetto,
read a histogram::

    from repro.obs import Tracer, tracing
    from repro.cluster import ClusterScheduler, iter_poisson_trace
    from repro.core.topology import RailXConfig

    tracer = Tracer(process="mlaas-demo")
    with tracing(tracer):                       # ambient: compiled_flow
        cfg = RailXConfig(m=4, n=4, R=64)       # spans land here too
        sched = ClusterScheduler(cfg, n=16)     # picks up the ambient tracer
        sched.run(iter_poisson_trace(seed=7, duration_s=6 * 3600.0,
                                     arrival_rate_per_h=12.0,
                                     mean_service_s=1800.0))

    tracer.write("run.json")        # open in https://ui.perfetto.dev —
    # one slice per scheduler event (event.JobSubmit, event.JobFinish,
    # ...), nested slices for placement attempts, OCS patch
    # apply/revert (stroke counts + downtime in the args), backlog
    # drains, and the flow engine's BFS/routing phases.

    # per-phase wall time (the perf-band harness's signal):
    print(tracer.phase_totals()["placement.attempt"])   # count/total_s/mean_us

    # the registry view: span durations as histograms + cache counters
    from repro.obs import MetricsRegistry
    reg = MetricsRegistry()
    sched2 = ClusterScheduler(cfg, n=16, registry=reg,
                              tracer=Tracer(registry=reg))
    sched2.run([...])
    reg.snapshot()["circuit_cache.hits"]        # replaces .hits attributes
    reg.snapshot()["span.placement.attempt"]    # {count, mean, p50, p99, ...}

The ``benchmarks/checks.py`` harness builds on all three: it replays the
BENCH matrices with tracing enabled, validates the emitted trace,
compares fidelity values byte-for-byte and enforces wall-time bands.
"""

from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .schema import KNOWN_SPANS, known_span_names, validate_trace
from .tracer import (
    NULL_TRACER,
    NullTracer,
    Tracer,
    get_tracer,
    set_tracer,
    tracing,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "Tracer",
    "get_tracer",
    "set_tracer",
    "tracing",
    "KNOWN_SPANS",
    "known_span_names",
    "validate_trace",
]
