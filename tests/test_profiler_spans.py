"""``train_loop``'s host spans land on the JAX profiler's trace, the clock
of the device's ops: per training step a ``train`` step annotation with
``train.data``, ``train.sync`` and ``train.checkpoint`` inside it, in
``train_loop`` itself and in the profile that ``launch/train.py
--profile-dir`` records.  The serving steps are the jitted functions, so
the dry run can ``lower`` them."""

import glob
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models.model_zoo import get_model
from repro.serve.serve_step import make_serve_step
from repro.train import optimizer as opt_lib
from repro.train.train_step import make_train_step
from repro.train.trainer import CheckpointPolicy, train_loop

B, S, CACHE = 2, 16, 32
SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")


@pytest.fixture(scope="module")
def zoo():
    return get_model(get_smoke_config("qwen3-8b"))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1,), ("data",))


@pytest.fixture(scope="module")
def serving(zoo, mesh):
    cache = jax.eval_shape(lambda: zoo.init_cache(B, CACHE))
    arts = make_serve_step(zoo, mesh, {"tokens": np.zeros((B, 1), np.int32)}, cache_example=cache)
    params = jax.device_put(zoo.init(jax.random.PRNGKey(0)), arts.param_sharding)
    return arts, params


def xplane_host_spans(directory):
    """(name, step number or None) of the events on the ``/host:CPU``
    plane of the one profile under ``directory``."""
    (path,) = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, dict(e.stats).get("step_num") if e.name == "train" else None)
            for p in data.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events]


def host_spans(fn):
    """``xplane_host_spans`` of a profile taken around ``fn()``."""
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        return xplane_host_spans(d)


def test_serving_steps_lower(zoo, serving):
    """``decode_fn.lower`` and ``prefill_fn.lower``: the dry run's path."""
    arts, params = serving
    cache = zoo.init_cache(B, CACHE)
    decode = arts.decode_fn.lower(params, cache, {"tokens": jnp.zeros((B, 1), jnp.int32)})
    prefill = arts.prefill_fn.lower(params, {"tokens": jnp.zeros((B, S), jnp.int32)})
    for lowered in (decode, prefill):
        assert lowered.compile().as_text()


def test_train_loop_steps_are_host_spans(zoo, mesh):
    ocfg = opt_lib.AdamWConfig()
    example = {"tokens": np.zeros((B, S), np.int32), "targets": np.zeros((B, S), np.int32)}
    arts = make_train_step(zoo, ocfg, mesh, example)
    params = jax.device_put(zoo.init(jax.random.PRNGKey(0)), arts.param_sharding)
    opt = jax.device_put(opt_lib.init(ocfg, params), arts.opt_sharding)
    batch = {k: jax.device_put(v, arts.batch_sharding[k]) for k, v in example.items()}
    params, opt, _ = arts.step_fn(params, opt, batch)       # compile outside

    with tempfile.TemporaryDirectory() as ckpt_dir:
        spans = host_spans(lambda: train_loop(
            arts.step_fn, params, opt, iter([batch] * 2), num_steps=2,
            ckpt=CheckpointPolicy(ckpt_dir, every_steps=2), log_fn=lambda _: None))
    names = [n for n, _ in spans]
    assert [step for n, step in spans if n == "train"] == [0, 1]
    assert names.count("train.data") == 2 and names.count("train.sync") == 2
    assert names.count("train.checkpoint") == 1


def test_launch_train_records_a_profile(tmp_path):
    """The operator's way to see the spans: ``launch/train.py
    --profile-dir`` around two steps with a checkpoint save."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.pathsep.join(filter(None, [SRC, os.environ.get("PYTHONPATH")])))
    env.pop("XLA_FLAGS", None)      # a device count another test set; --devices sets its own
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-8b", "--smoke",
         "--devices", "1", "--steps", "2", "--seq-len", "16", "--global-batch", "2",
         "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "2",
         "--profile-dir", str(tmp_path / "profile")],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    spans = xplane_host_spans(str(tmp_path / "profile"))
    names = [n for n, _ in spans]
    assert [step for n, step in spans if n == "train"] == [0, 1]
    assert names.count("train.data") == 2 and names.count("train.sync") == 2
    assert names.count("train.checkpoint") == 1
