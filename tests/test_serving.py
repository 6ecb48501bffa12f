"""ISSUE 10: the MLaaS serving digital twin.

The load-bearing guarantees:

* the diurnal trace generator is seeded-deterministic, streams lazily
  (iterator == materialized list), and conserves the rate integral
  exactly against the closed-form ``Lambda(t)`` with bursts off;
* ``ServiceModel`` is strictly monotone in the surviving-rail factor —
  degraded circuits always hurt decode, KV streaming, and the
  steady-state replica rate;
* the M/M/c queue figures (Erlang-C, wait profile, SLO attainment) obey
  their textbook shapes, and the autoscaler sizing respects min/max;
* the scheduler hooks are default-off: ``serving=None``, the omitted
  kwarg, and an empty ``ServingConfig`` all schedule byte-identically,
  and ``summary()`` grows no serving keys;
* end to end, the autoscaler measurably beats the fixed-replica
  baseline's SLO attainment on the same seed; manual ``ReplicaScale``
  events clamp to min/max; switch faults degrade replicas in place and
  the recover heals them; serving preemption priority evicts training
  and the headroom reserve blocks training placement;
* torus-3d registers ``job_network`` (it joins the chaos/serving
  sweeps — the printed operable/skip rosters are pinned), folding each
  subgroup line into a sub-torus that degenerates to the 2-D ring for
  short lines;
* a traced serving run validates against the Chrome schema and emits
  the serving event + policy spans, and the serving modules are
  repro-lint clean.
"""

import json
import math
import os
import sys
from pathlib import Path

import pytest

from repro.cluster import (
    ClusterScheduler,
    DiurnalProfile,
    JobSubmit,
    RateUpdate,
    ReplicaScale,
    ServiceModel,
    ServingConfig,
    SwitchFail,
    SwitchRecover,
    cumulative_requests,
    diurnal_trace,
    desired_replicas,
    erlang_c,
    iter_diurnal_trace,
    make_job,
    make_service,
    mean_diurnal_rate,
    mmc_wait_profile,
    plan_job_mapping,
    slo_attainment,
)
from repro.core.availability import JobAllocation
from repro.core.topology import RailXConfig

ROOT = Path(__file__).resolve().parents[1]

CFG = RailXConfig(m=4, n=4, R=32)   # 16x16 node grid, r=16 rails
SIDE = 16


def _sched(**kw):
    kw.setdefault("goodput_model", "none")
    kw.setdefault("validate_circuits", False)
    return ClusterScheduler(CFG, n=SIDE, policy="best_fit", **kw)


def _service(**kw):
    kw.setdefault("slo_p99_s", 2.0)
    kw.setdefault("initial_replicas", 1)
    kw.setdefault("max_replicas", 6)
    return make_service(0, "qwen3-8b", **kw)


def _fingerprint(m, sched):
    return json.dumps(
        {
            "summary": m.summary(),
            "jobs": sorted(
                (jid, rec.submit_t, rec.finish_t, rec.migrations,
                 rec.shrinks, rec.repairs, round(rec.lost_work_s, 9))
                for jid, rec in m.records.items()
            ),
            "backlog": [j.job_id for j in sched.backlog],
        },
        sort_keys=True,
    )


# ---------------------------------------------------------------------------
# Diurnal trace generator (satellite 3)
# ---------------------------------------------------------------------------


class TestDiurnalTraces:
    KW = dict(
        service_id=3, duration_s=6 * 3600.0, interval_s=300.0,
        profile=DiurnalProfile(base_rps=12.0),
    )

    def test_seeded_determinism(self):
        a = diurnal_trace(seed=11, burst_prob=0.3, **self.KW)
        b = diurnal_trace(seed=11, burst_prob=0.3, **self.KW)
        c = diurnal_trace(seed=12, burst_prob=0.3, **self.KW)
        assert a == b
        assert a != c

    def test_stream_matches_list(self):
        it = iter_diurnal_trace(seed=5, burst_prob=0.4, **self.KW)
        assert list(it) == diurnal_trace(seed=5, burst_prob=0.4, **self.KW)

    def test_burst_off_draws_nothing(self):
        """burst_prob=0.0 (the default) never touches the RNG: any two
        seeds produce the identical closed-form stream."""
        assert diurnal_trace(seed=1, **self.KW) == diurnal_trace(
            seed=999, **self.KW
        )

    def test_rate_integral_conservation(self):
        """Bursts off, the piecewise-constant trace integrates to the
        closed-form ``Lambda(duration)`` exactly: each bin carries its
        exact average rate."""
        events = diurnal_trace(seed=0, **self.KW)
        total = sum(
            e.rate_rps * (events[i + 1].time - e.time)
            for i, e in enumerate(events[:-1])
        )
        expect = cumulative_requests(self.KW["profile"], self.KW["duration_s"])
        assert math.isclose(total, expect, rel_tol=1e-9)

    def test_mean_rate_closed_form(self):
        """Over one full day every default harmonic completes whole
        periods, so the mean collapses to the base rate."""
        profile = DiurnalProfile(base_rps=9.0)
        assert math.isclose(
            mean_diurnal_rate(profile, 86400.0), 9.0, rel_tol=1e-9
        )

    def test_shape_and_closing_sample(self):
        events = diurnal_trace(seed=0, **self.KW)
        assert all(isinstance(e, RateUpdate) for e in events)
        assert all(e.service_id == 3 for e in events)
        times = [e.time for e in events]
        assert times == sorted(times) and len(set(times)) == len(times)
        assert events[-1].time == self.KW["duration_s"]
        assert events[-1].rate_rps == 0.0
        assert len(events) == int(6 * 3600 / 300) + 1

    def test_bursts_bounded_and_nonnegative(self):
        base = diurnal_trace(seed=4, **self.KW)
        burst = diurnal_trace(seed=4, burst_prob=1.0, burst_mult=3.0,
                              **self.KW)
        for quiet, spiky in zip(base[:-1], burst[:-1]):
            assert quiet.rate_rps <= spiky.rate_rps
            assert spiky.rate_rps <= quiet.rate_rps * 3.0 + 1e-12

    def test_bad_interval_rejected(self):
        with pytest.raises(ValueError, match="interval_s"):
            next(iter_diurnal_trace(service_id=0, interval_s=0.0))

    def test_serving_modules_are_lint_clean(self):
        """The new modules pass the repro-lint invariant analyzer with
        zero findings — no unseeded RNG, wall-clock reads, unguarded
        tracer args, or frozen-dataclass mutation."""
        sys.path.insert(0, str(ROOT))
        try:
            from tools.lint import lint_source
        finally:
            sys.path.remove(str(ROOT))
        for rel in (
            "src/repro/cluster/serving.py",
            "src/repro/cluster/serving_traces.py",
        ):
            src = (ROOT / rel).read_text()
            findings = lint_source(src, path=rel, root=str(ROOT))
            assert not findings, [f.format() for f in findings]


# ---------------------------------------------------------------------------
# Roofline-backed service model
# ---------------------------------------------------------------------------


class TestServiceModel:
    SPEC = _service()
    MODEL = ServiceModel.for_spec(SPEC)

    def test_rail_factor_strictly_monotone(self):
        """Fewer surviving rails always hurts: decode step time strictly
        rises, KV streaming strictly rises, replica rate strictly falls."""
        factors = (1.0, 0.8, 0.5, 0.25)
        steps = [
            self.MODEL.decode_step_s(8, 1152.0, rail_factor=f)
            for f in factors
        ]
        rates = [
            self.MODEL.replica_rate_rps(self.SPEC, rail_factor=f)
            for f in factors
        ]
        assert steps == sorted(steps) and len(set(steps)) == len(steps)
        assert rates == sorted(rates, reverse=True)
        assert len(set(rates)) == len(rates)
        assert all(r > 0.0 for r in rates)

    def test_kv_stream_scales_inversely_with_rails(self):
        one = self.MODEL.kv_stream_s(1024.0, rail_factor=1.0)
        half = self.MODEL.kv_stream_s(1024.0, rail_factor=0.5)
        assert math.isclose(half, 2.0 * one, rel_tol=1e-12)

    def test_service_time_decomposition(self):
        """A request costs at least its decode steps plus KV shipping."""
        spec = self.SPEC
        context = spec.prompt_tokens + spec.tokens_per_request / 2.0
        step = self.MODEL.decode_step_s(spec.batch_size, context)
        svc = self.MODEL.request_service_s(spec)
        assert svc >= spec.tokens_per_request * step
        assert self.MODEL.tokens_per_s(spec.batch_size, context) > 0.0


class TestQueueMath:
    def test_erlang_c_shape(self):
        assert erlang_c(4, 0.0) == 0.0
        assert erlang_c(4, 4.0) == 1.0
        assert erlang_c(4, 5.0) == 1.0
        loads = [0.5, 1.0, 2.0, 3.0, 3.9]
        probs = [erlang_c(4, a) for a in loads]
        assert probs == sorted(probs)
        assert all(0.0 <= p <= 1.0 for p in probs)
        with pytest.raises(ValueError, match="server"):
            erlang_c(0, 1.0)

    def test_mmc_wait_profile(self):
        pc4, mean4, p99_4 = mmc_wait_profile(3.0, 1.0, 4)
        pc8, mean8, p99_8 = mmc_wait_profile(3.0, 1.0, 8)
        assert mean8 < mean4 and pc8 < pc4 and p99_8 <= p99_4
        with pytest.raises(ValueError, match="unstable"):
            mmc_wait_profile(4.0, 1.0, 4)

    def test_slo_attainment_shape(self):
        assert slo_attainment(3.0, 1.0, 4, 0.5) == 0.0   # slo < service
        assert slo_attainment(5.0, 1.0, 4, 10.0) == 0.0  # saturated
        slos = [1.5, 2.0, 4.0, 10.0]
        atts = [slo_attainment(3.0, 1.0, 4, s) for s in slos]
        assert atts == sorted(atts)
        assert all(0.0 <= a <= 1.0 for a in atts)
        assert atts[-1] > 0.99

    def test_desired_replicas_clamps(self):
        spec = _service(min_replicas=2, max_replicas=5)
        assert desired_replicas(spec, 0.0, 10.0, 0.7) == 2
        assert desired_replicas(spec, 1e9, 10.0, 0.7) == 5
        assert desired_replicas(spec, 21.0, 10.0, 0.7) == 3
        # degenerate inputs fall back to the floor
        assert desired_replicas(spec, 5.0, 0.0, 0.7) == 2


# ---------------------------------------------------------------------------
# Scheduler integration
# ---------------------------------------------------------------------------


class TestSchedulerServing:
    def test_initial_placement(self):
        sched = _sched(serving=ServingConfig(
            services=(_service(initial_replicas=2),),
        ))
        st = sched.services[0]
        assert len(st.replicas) == 2
        assert all(rep.factor == 1.0 for rep in st.replicas)
        assert sched._occ.free_count < SIDE * SIDE

    def test_flags_off_byte_identity(self):
        """serving=None, the omitted kwarg, and an empty ServingConfig
        all schedule byte-identically, and summary() grows no keys."""
        events = [
            JobSubmit(time=i * 100.0,
                      job=make_job(i, "qwen3-8b", service_s=3600.0))
            for i in range(4)
        ]
        prints = []
        for kw in ({}, {"serving": None}, {"serving": ServingConfig()}):
            sched = _sched(**kw)
            m = sched.run(list(events))
            prints.append(_fingerprint(m, sched))
        assert prints[0] == prints[1] == prints[2]
        summary = _sched().run([]).summary()
        assert not any("serving" in k or "slo" in k for k in summary)

    def test_manual_replica_scale_clamps(self):
        sched = _sched(serving=ServingConfig(
            services=(_service(min_replicas=1, max_replicas=4),),
        ))
        st = sched.services[0]
        sched.run([ReplicaScale(time=10.0, service_id=0,
                                target_replicas=3)], until=10.0)
        assert len(st.replicas) == 3
        sched.run([ReplicaScale(time=20.0, service_id=0,
                                target_replicas=99)], until=20.0)
        assert len(st.replicas) == 4        # clamped to max
        sched.run([ReplicaScale(time=30.0, service_id=0,
                                target_replicas=0)], until=30.0)
        assert len(st.replicas) == 1        # clamped to min
        srv = sched.serving_summary(until=30.0)
        assert srv["scale_ups"] == 3 and srv["scale_downs"] == 3
        assert srv["replica_scale_events"] == 3
        # unknown service ids are ignored, not fatal
        sched.run([ReplicaScale(time=40.0, service_id=7,
                                target_replicas=2)], until=40.0)
        assert len(st.replicas) == 1

    def _mixed_run(self, *, autoscale):
        profile = DiurnalProfile(base_rps=20.0)
        events = diurnal_trace(
            service_id=0, seed=7, duration_s=4 * 3600.0,
            interval_s=600.0, profile=profile,
        )
        sched = _sched(serving=ServingConfig(
            services=(_service(),), autoscale=autoscale,
        ))
        sched.run(list(events))
        return sched.serving_summary(until=4 * 3600.0)

    def test_autoscaler_beats_fixed_baseline(self):
        """Same seed, same diurnal demand (peaking near 3x one replica's
        throughput): the autoscaler's SLO attainment must measurably
        beat the fixed single-replica baseline's."""
        fixed = self._mixed_run(autoscale=False)
        auto = self._mixed_run(autoscale=True)
        assert fixed["replica_scale_events"] == 0
        assert auto["scale_ups"] > 0
        assert auto["slo_attainment"] > fixed["slo_attainment"] + 0.1
        assert auto["p99_queue_delay_s"] < fixed["p99_queue_delay_s"]

    def test_switch_fault_degrades_then_heals(self):
        sched = _sched(serving=ServingConfig(services=(_service(),)))
        st = sched.services[0]
        key = next(iter(st.replicas[0].circuits))
        sched.run([SwitchFail(time=100.0, switch=key)], until=100.0)
        srv = sched.serving_summary(until=100.0)
        touched = (
            srv["serving_repairs"] + srv["serving_migrations"]
            + srv["serving_fault_evictions"]
        )
        assert touched > 0
        degraded = [rep.factor for rep in st.replicas]
        if srv["serving_repairs"]:
            assert any(f < 1.0 for f in degraded)
        sched.run([SwitchRecover(time=200.0, switch=key)], until=200.0)
        assert all(rep.factor == 1.0 for rep in st.replicas)

    def test_headroom_reserve_blocks_training(self):
        job = make_job(0, "qwen3-8b", service_s=3600.0)
        submit = JobSubmit(time=10.0, job=job)
        open_sched = _sched(serving=ServingConfig(
            services=(_service(),), headroom_nodes=0,
        ))
        open_sched.run([submit], until=10.0)
        assert 0 in open_sched.running
        reserved = _sched(serving=ServingConfig(
            services=(_service(),), headroom_nodes=SIDE * SIDE,
        ))
        reserved.run([JobSubmit(time=10.0, job=job)], until=10.0)
        assert 0 not in reserved.running
        assert [j.job_id for j in reserved.backlog] == [0]

    def _packed(self, *, preempt):
        from repro.cluster import default_serve_plan

        sched = _sched(serving=ServingConfig(
            services=(_service(),), preempt_training=preempt,
        ))
        # pack every free cell with 2-node training jobs (same footprint
        # as a replica) so a scale-up can only land by evicting one
        plan = default_serve_plan("qwen3-8b")
        events = [
            JobSubmit(time=0.0, job=make_job(
                i, "qwen3-8b", plan=plan, service_s=1e6,
            ))
            for i in range(140)
        ]
        sched.run(events, until=0.0)
        assert sched._occ.free_count == 0
        sched.run([ReplicaScale(time=50.0, service_id=0,
                                target_replicas=2)], until=50.0)
        return sched

    def test_preemption_priority_evicts_training(self):
        """On a packed grid a scale-up can only land by evicting
        strictly-lower-tier training (serving tier outranks the make_job
        default); with the flag off it must fail instead."""
        sched = self._packed(preempt=True)
        srv = sched.serving_summary(until=50.0)
        assert len(sched.services[0].replicas) == 2
        assert srv["serving_preemptions"] > 0
        assert srv["scale_failures"] == 0
        sched = self._packed(preempt=False)
        srv = sched.serving_summary(until=50.0)
        assert len(sched.services[0].replicas) == 1
        assert srv["scale_failures"] > 0
        assert srv["serving_preemptions"] == 0

    def test_serving_summary_structure(self):
        sched = _sched(serving=ServingConfig(services=(_service(),)))
        sched.run(
            [RateUpdate(time=0.0, service_id=0, rate_rps=5.0)],
            until=0.0,
        )
        srv = sched.serving_summary(until=600.0)
        assert srv["requests"] > 0
        assert 0.0 <= srv["slo_attainment"] <= 1.0
        per = srv["services"]["0"]
        assert per["replicas"] == 1
        assert per["slo_p99_s"] == 2.0


# ---------------------------------------------------------------------------
# torus-3d job network (satellite 1)
# ---------------------------------------------------------------------------


class TestTorus3dJobNetwork:
    def _nets(self, arch="qwen3-8b"):
        from repro.cluster.metrics import (
            build_job_network_torus,
            build_job_network_torus3d,
        )

        job = make_job(0, arch, service_s=100.0)
        jmap = plan_job_mapping(CFG, job)
        alloc = JobAllocation(
            rows=tuple(range(jmap.rows_req)),
            cols=tuple(range(jmap.cols_req)),
        )
        t2 = build_job_network_torus(CFG, jmap.mapping, alloc)
        t3 = build_job_network_torus3d(CFG, jmap.mapping, alloc)
        return t2, t3

    def test_fold_adds_chords_conserving_trunk(self):
        """Where a subgroup line folds, the 3-D torus re-spends the same
        rail trunk as ring (2/3) + stride-k chords (1/3): total link
        capacity is conserved while the edge set strictly grows."""
        t2, t3 = self._nets()
        cap2, cap3 = sum(t2.capacity.values()), sum(t3.capacity.values())
        if len(t3.capacity) == len(t2.capacity):
            pytest.skip("mapping produced no foldable subgroup")
        assert len(t3.capacity) > len(t2.capacity)
        assert math.isclose(cap2, cap3, rel_tol=1e-9)
        assert set(t2.capacity) <= set(t3.capacity)

    def test_torus3d_schedules_with_flow_goodput(self):
        sched = _sched(goodput_model="flow", fabric="torus-3d")
        sched.run([JobSubmit(
            time=0.0, job=make_job(0, "qwen3-8b", service_s=100.0),
        )])
        m = sched.metrics
        assert m.records[0].finish_t is not None
        assert m.summary()["utilization"] > 0.0

    def test_operable_roster_regression(self, capsys):
        """torus-3d joins the chaos/serving sweeps; the printed operable
        and skip rosters are pinned so a capability regression in any
        fabric shows up as a diff here, not as a silent skip."""
        sys.path.insert(0, str(ROOT / "benchmarks"))
        try:
            import bench_chaos
            import bench_serving
        finally:
            sys.path.remove(str(ROOT / "benchmarks"))
        operable, skipped = bench_chaos.chaos_fabrics()
        assert operable == [
            "railx-hyperx", "torus-2d", "torus-3d", "rail-only",
        ]
        assert skipped == [
            "fat-tree-nonblocking", "fat-tree-tapered", "dragonfly",
            "hammingmesh", "rail-only-2d-ft", "ub-mesh-2level",
        ]
        bench_chaos.announce_fabrics()
        bench_serving.announce_fabrics()
        out = capsys.readouterr().out.splitlines()
        assert out == [
            "bench_chaos fabrics: railx-hyperx,torus-2d,torus-3d,rail-only",
            "bench_chaos skipping (no job_network capability): "
            "fat-tree-nonblocking,fat-tree-tapered,dragonfly,hammingmesh,"
            "rail-only-2d-ft,ub-mesh-2level",
            "bench_serving fabrics: railx-hyperx,torus-2d,torus-3d,rail-only",
            "bench_serving skipping (no job_network capability): "
            "fat-tree-nonblocking,fat-tree-tapered,dragonfly,hammingmesh,"
            "rail-only-2d-ft,ub-mesh-2level",
        ]


# ---------------------------------------------------------------------------
# Observability (satellite 2)
# ---------------------------------------------------------------------------


class TestServingObservability:
    def test_traced_run_emits_serving_spans(self):
        from repro.obs import Tracer, tracing, validate_trace

        profile = DiurnalProfile(base_rps=20.0)
        events = diurnal_trace(
            service_id=0, seed=3, duration_s=3600.0,
            interval_s=600.0, profile=profile,
        )
        tracer = Tracer(process="test-serving")
        with tracing(tracer):
            sched = _sched(serving=ServingConfig(
                services=(_service(),), autoscale=True,
            ))
            sched.run(list(events))
        trace = tracer.to_dict()
        stats = validate_trace(trace)
        assert stats["events"] > 0 and stats["instants"] > 0
        names = tracer.span_names()
        # the autoscale decision is an instant — span_names and the
        # phase aggregate must both see it (the checks.py protocol)
        assert tracer.phase_totals()["serving.autoscale"]["count"] > 0
        for required in (
            "event.RateUpdate", "event.ReplicaScale",
            "serving.autoscale", "serving.place",
        ):
            assert required in names, f"missing span {required}"

    def test_serving_spans_cataloged(self):
        from repro.obs import known_span_names

        catalog = known_span_names()
        for name in (
            "serving.autoscale", "serving.place", "roofline.parse",
        ):
            assert name in catalog
