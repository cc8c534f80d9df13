"""Training substrate: convergence, checkpoint integrity, restart."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import InputShape
from repro.configs.registry import smoke_config
from repro.data.synthetic import token_stream
from repro.models import model as M
from repro.models.params import init_params
from repro.train import adamw
from repro.train import checkpoint as CKPT
from repro.train.trainer import HeartbeatMonitor, fit, run_with_restarts

# long-running tier: excluded from CI fast job (-m 'not slow')
pytestmark = pytest.mark.slow

SHAPE = InputShape("tiny", 32, 4, "train")


def test_loss_decreases():
    cfg = smoke_config("qwen3-8b")
    # fixed repeating batch -> the model must fit it
    batch = next(token_stream(cfg.vocab_size, 4, 32, seed=0))
    rep = fit(cfg, SHAPE, iter(lambda: batch, None), 30, log_every=0)
    first = np.mean(rep.losses[:5])
    last = np.mean(rep.losses[-5:])
    assert last < first - 0.2, (first, last)


def test_checkpoint_roundtrip(tmp_path):
    cfg = smoke_config("mamba2-370m")
    params = init_params(M.param_defs(cfg), jax.random.key(0))
    opt = adamw.init(params)
    CKPT.save(str(tmp_path), 7, (params, opt))
    (p2, o2), step = CKPT.restore(str(tmp_path), (params, opt))
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomic_gc(tmp_path):
    tree = {"w": jnp.arange(10.0)}
    for s in (1, 2, 3, 4, 5):
        CKPT.save(str(tmp_path), s, tree, keep=2)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(steps) == 2
    assert CKPT.latest_step(str(tmp_path)) == 5


def test_checkpoint_detects_corruption(tmp_path):
    tree = {"w": jnp.arange(10.0)}
    d = CKPT.save(str(tmp_path), 1, tree)
    # flip a byte in the leaf file
    f = os.path.join(d, "arr_00000.npy")
    data = bytearray(open(f, "rb").read())
    data[-1] ^= 0xFF
    open(f, "wb").write(bytes(data))
    with pytest.raises(IOError):
        CKPT.restore(str(tmp_path), tree)


def test_run_with_restarts_recovers(tmp_path):
    """Fault injection: the supervised loop restores and finishes."""
    state = {"x": jnp.zeros(())}
    fail_at = {3, 7}

    def step_fn(s, step):
        if step in fail_at:
            fail_at.discard(step)
            raise RuntimeError(f"injected failure at {step}")
        return {"x": s["x"] + 1.0}

    final, rep = run_with_restarts(step_fn, state, 10,
                                   ckpt_dir=str(tmp_path), ckpt_every=2)
    assert rep.steps_done == 10
    assert rep.n_restores == 2
    assert float(final["x"]) == 10.0


def test_heartbeat_straggler_detection():
    mon = HeartbeatMonitor(8, z_thresh=2.5)
    for step in range(6):
        for w in range(8):
            t = 1.0 if w != 5 else 3.5   # worker 5 is slow
            mon.beat(w, t, now=float(step))
    assert mon.stragglers() == [5]
    # worker 3 stops beating -> dead after timeout
    for step in range(6, 9):
        for w in range(8):
            if w != 3:
                mon.beat(w, 1.0, now=float(step) * 5)
    assert 3 in mon.dead(now=100.0)


def test_perf_flags_numerics_equivalence():
    """§Perf flags (bf16 gathers + TP unembed + sharded CE) must not
    change the math — loss/grad-norm agree to bf16 tolerance."""
    import subprocess
    import sys
    import textwrap
    code = """
        import os, sys
        flags = sys.argv[1] == "on"
        if flags:
            os.environ["REPRO_LOSS_UNEMBED_TP"] = "1"
            os.environ["REPRO_CAST_PARAMS_ONCE"] = "1"
            os.environ["REPRO_SHARDED_CE"] = "1"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import smoke_config
        from repro.configs.base import InputShape
        from repro.models import model as M
        from repro.models.params import init_params
        from repro.train import adamw
        from repro.train.train_step import make_train_step
        cfg = smoke_config("qwen3-8b").replace(vocab_size=512)
        shape = InputShape("t", 1024, 8, "train")
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        step, in_sh, out_sh, _ = make_train_step(cfg, shape, mesh)
        params = init_params(M.param_defs(cfg), jax.random.key(0))
        opt = adamw.init(params)
        rng = np.random.default_rng(0)
        batch = {k: jnp.asarray(rng.integers(0, 512, (8, 1024)), jnp.int32)
                 for k in ("tokens", "labels")}
        with mesh:
            _, _, m = jax.jit(step, in_shardings=in_sh,
                              out_shardings=out_sh)(params, opt, batch)
        print(float(m["loss"]))
    """
    losses = []
    for arg in ("off", "on"):
        # JAX_PLATFORMS=cpu is load-bearing: without it, boxes with a
        # libtpu install spin for minutes retrying TPU metadata fetches
        res = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(code), arg],
            capture_output=True, text=True, timeout=600,
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                 "HOME": "/root",
                 "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
        assert res.returncode == 0, res.stderr[-2000:]
        losses.append(float(res.stdout.strip().splitlines()[-1]))
    assert abs(losses[0] - losses[1]) < 1e-4, losses
