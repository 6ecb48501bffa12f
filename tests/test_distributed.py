"""Distributed-path tests: run in subprocesses with forced host device
counts (never set globally per the assignment)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TESTS = os.path.abspath(os.path.dirname(__file__))


def run_py(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_collective_schedules_equivalence():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.collectives import make_all_reduce_fn
        from repro.launch.mesh import make_mesh as _mk_mesh
        mesh = _mk_mesh((4, 2), ("node", "mesh"))
        x = jnp.array(np.random.RandomState(0).randn(32, 16), jnp.float32)
        xs = jax.device_put(x, NamedSharding(mesh, P("node", None)))
        ref = 2 * x.reshape(4, 8, 16).sum(0)
        errs = {}
        for sched in ("flat", "hierarchical", "ring2d"):
            fn = make_all_reduce_fn(mesh, P("node", None), sched,
                                    intra_axes="mesh", inter_axes="node")
            out = fn(xs)
            local = np.asarray(jax.device_get(out.addressable_shards[0].data))
            errs[sched] = float(np.abs(local - ref).max())
        print(json.dumps(errs))
    """)
    errs = json.loads(out.strip().splitlines()[-1])
    assert all(v < 1e-4 for v in errs.values()), errs


def test_hierarchical_reduces_inter_node_bytes():
    """The paper's Eq. 8 claim, measured in compiled HLO: the inter-axis
    all-reduce payload shrinks by |intra| with the hierarchical schedule."""
    out = run_py("""
        import jax, jax.numpy as jnp, re, json
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.collectives import make_all_reduce_fn
        from repro.launch.mesh import make_mesh as _mk_mesh
        mesh = _mk_mesh((2, 4), ("node", "mesh"))
        sds = jax.ShapeDtypeStruct((16, 64), jnp.float32,
                sharding=NamedSharding(mesh, P("node", None)))
        def ar_bytes(sched):
            fn = make_all_reduce_fn(mesh, P("node", None), sched,
                                    intra_axes="mesh", inter_axes="node")
            txt = fn.lower(sds).compile().as_text()
            total = 0
            for m in re.finditer(r"= \\S*?f32\\[([\\d,]*)\\][^\\n]*? all-reduce\\(", txt):
                dims = [int(d) for d in m.group(1).split(",") if d]
                n = 1
                for d in dims: n *= d
                total += n * 4
            return total
        print(json.dumps({"flat": ar_bytes("flat"), "hier": ar_bytes("hierarchical")}))
    """)
    data = json.loads(out.strip().splitlines()[-1])
    assert data["hier"] * 3 < data["flat"], data  # ~4x fewer AR bytes


def _train_modes_losses(attn_impl: str) -> dict:
    """Three steps of gspmd_fsdp and of manual_hier + hierarchical on a
    (pod, data, model) = (2, 2, 2) mesh, same parameters and batches."""
    out = run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np, json
        from repro.configs import get_smoke_config
        from repro.models.model_zoo import get_model
        from repro.train.optimizer import AdamWConfig, init as opt_init
        from repro.train.train_step import make_train_step
        from repro.data.pipeline import DataConfig, SyntheticLM
        from repro.launch.mesh import make_mesh as _mk_mesh
        mesh = _mk_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = dataclasses.replace(get_smoke_config("qwen3-8b"), attn_impl=%r)
        zoo = get_model(cfg)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8))
        out = {}
        for mode, sched in (("gspmd_fsdp","n/a"), ("manual_hier","hierarchical")):
            arts = make_train_step(zoo, ocfg, mesh, data.batch(0),
                                   dp_mode=mode, schedule=sched)
            p = jax.device_put(zoo.init(jax.random.PRNGKey(0)), arts.param_sharding)
            o = jax.device_put(opt_init(ocfg, zoo.init(jax.random.PRNGKey(0))),
                               arts.opt_sharding)
            losses = []
            for s in range(3):
                b = {k: jax.device_put(v, arts.batch_sharding[k])
                     for k, v in data.batch(s).items()}
                p, o, m = arts.step_fn(p, o, b)
                losses.append(float(m["loss"]))
            out[mode] = losses
        print(json.dumps(out))
    """ % attn_impl)
    return json.loads(out.strip().splitlines()[-1])


def test_train_modes_agree():
    """The explicit RailX schedule (``manual_hier`` + hierarchical: RS on
    ``data``, AR on ``pod``, AG on ``data`` inside a partial-manual
    shard_map) trains to the same losses as the GSPMD FSDP step."""
    data = _train_modes_losses("ref")
    a = data["gspmd_fsdp"]
    b = data["manual_hier"]
    assert all(abs(x - y) < 1e-3 for x, y in zip(a, b)), data
    assert a[-1] < a[0]  # learning


def test_train_modes_agree_flash():
    """Same, with the flash kernel: inside manual_hier's region it runs per
    shard, in a nested shard_map over the remaining ``model`` axis."""
    data = _train_modes_losses("flash")
    a = data["gspmd_fsdp"]
    b = data["manual_hier"]
    assert all(abs(x - y) < 1e-3 for x, y in zip(a, b)), data
    assert a[-1] < a[0]  # learning


def test_moe_ep_matches_dense():
    """EP shard_map MoE == dense oracle when capacity is not binding."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.models.moe import MoEConfig, init_moe, moe_ffn_dense, moe_ffn_ep
        from repro.models.common import DTypes
        from repro.launch.mesh import make_mesh as _mk_mesh
        mesh = _mk_mesh((4,), ("data",))
        cfg = MoEConfig(d_model=32, d_ff=16, num_experts=8, top_k=2,
                        capacity_factor=8.0)
        dt = DTypes()
        p = init_moe(jax.random.PRNGKey(0), cfg, dt)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 32))
        dense, aux_d = moe_ffn_dense(p, cfg, x, dt)
        ep, aux_e = jax.jit(lambda p, x: moe_ffn_ep(p, cfg, x, dt, mesh))(p, x)
        err = float(jnp.abs(dense - ep).max())
        print(json.dumps({"err": err, "aux_d": float(aux_d), "aux_e": float(aux_e)}))
    """)
    data = json.loads(out.strip().splitlines()[-1])
    assert data["err"] < 2e-4, data


def test_pipeline_parallel_forward():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.parallel.pipeline import make_pipelined_apply
        from repro.launch.mesh import make_mesh as _mk_mesh
        mesh = _mk_mesh((4,), ("pipe",))
        # 4 stages, each multiplies by its stage weight
        ws = jnp.stack([jnp.eye(8) * (i + 1) for i in range(4)])
        def stage(w, x):
            return x @ w
        fn = make_pipelined_apply(mesh, stage, num_micro=6, axis="pipe")
        xs = jax.random.normal(jax.random.PRNGKey(0), (6, 3, 8))
        out = fn(ws, xs)
        ref = xs * 1 * 2 * 3 * 4
        print(json.dumps({"err": float(jnp.abs(out - ref).max())}))
    """)
    data = json.loads(out.strip().splitlines()[-1])
    assert data["err"] < 1e-4, data


def test_gradient_collectives_sit_under_grad_reduce():
    """``manual_hier`` on a (pod, data) = (2, 2) mesh: every collective
    that moves gradients is under the ``grad_reduce`` scope, for each RailX
    schedule; the only others are the scalar means of the loss and its
    metrics."""
    out = run_py("""
        import json, sys
        sys.path.insert(0, %r)
        import jax, numpy as np
        import hlo_scopes as scopes
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.models.model_zoo import get_model
        from repro.train import optimizer as opt_lib
        from repro.train.train_step import make_train_step
        mesh = make_mesh((2, 2), ("pod", "data"))
        zoo = get_model(get_smoke_config("qwen3-8b"))
        ocfg = opt_lib.AdamWConfig()
        ex = {"tokens": np.zeros((8, 16), np.int32), "targets": np.zeros((8, 16), np.int32)}
        params = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0)))
        shapes = lambda t, s: jax.tree_util.tree_map(
            lambda a, x: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=x), t, s)
        out = {}
        for sched in ("flat", "hierarchical", "compressed"):
            arts = make_train_step(zoo, ocfg, mesh, ex, dp_mode="manual_hier", schedule=sched)
            batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=arts.batch_sharding[k])
                     for k, v in ex.items()}
            hlo = arts.step_fn.lower(
                shapes(params, arts.param_sharding),
                shapes(jax.eval_shape(lambda p: opt_lib.init(ocfg, p), params), arts.opt_sharding),
                batch).compile().as_text()
            out[sched] = [[scopes.scope_of(ins.op_name), scopes.is_scalar(ins)]
                          for ins in scopes.instructions(hlo) if scopes.is_collective(ins)]
        print(json.dumps(out))
    """ % TESTS, devices=4)
    found = json.loads(out.strip().splitlines()[-1])
    for sched, colls in found.items():
        assert any(scope == "grad_reduce" for scope, _ in colls), (sched, colls)
        assert all(scope == "grad_reduce" or scalars for scope, scalars in colls), (sched, colls)
