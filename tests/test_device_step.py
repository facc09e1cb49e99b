"""``launch/train.py --scale device``: the production group-mode step run
with real arrays on the local devices, at a small size on the CPU.

The timed size runs on the chip (``chip_smoke.py``); here the reduced
minitron config checks the wiring, the 2x2-mesh layouts and that
shard-local and gathered aggregation give the same step."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

from repro import optim
from repro.configs import get_chip_share, get_config
from repro.configs import minitron_4b
from repro.core import RobustConfig
from repro.core.robust_train import aggregate_reported
from repro.data.tokens import TokenStream
from repro.launch import steps, train
from repro.models import model as model_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(k=4, batch=8, seq=32):
    cfg = get_config("minitron-4b").reduced()
    rc = RobustConfig(num_workers=k, num_byzantine=1, num_batches=k,
                      attack="sign_flip", aggregator="gmom")
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=seq,
                         global_batch=batch, num_workers=k, seed=0)
    return cfg, rc, stream


def test_run_device_steps_one_device():
    cfg, rc, stream = _setup()
    run = train.run_device_steps(cfg, rc, optim.adamw(1e-3), stream,
                                 steps=3, seed=0)
    losses = [h["loss_mean"] for h in run["history"]]
    assert len(losses) == 3 and np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert all(h["byz_count"] == 1 for h in run["history"])
    assert run["compile_seconds"] > 0 and len(run["step_seconds"]) == 3
    # the same seed gives the same run
    again = train.run_device_steps(cfg, rc, optim.adamw(1e-3), stream,
                                   steps=3, seed=0)
    assert again["history"] == run["history"]


def test_step_halves_reproduce_the_step():
    """``make_group_grads`` then ``report_groups`` and the aggregation give
    the aggregate the step hands AdamW (its first moment over 1 - b1): the
    four-chip smoke compares layouts half by half on that basis."""
    cfg, rc, stream = _setup()
    run = train.run_device_steps(cfg, rc, optim.adamw(1e-3, b1=0.9), stream,
                                 steps=1, seed=0)
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    key = train.device_step_key(0, 0)
    losses, grads = steps.make_group_grads(cfg)(params, stream.batch(0))
    reported, mask = steps.report_groups(grads, rc, key, 0)
    agg = aggregate_reported(reported, rc, key=key)
    assert int(mask.sum()) == 1
    assert np.isclose(float(losses.mean()), run["history"][0]["loss_mean"])
    for a, m in zip(jax.tree.leaves(agg), jax.tree.leaves(run["opt_state"].mu)):
        np.testing.assert_allclose(a, m / (1 - 0.9), rtol=1e-5, atol=1e-8)


def test_device_mode_cli(monkeypatch, tmp_path):
    """``--scale device`` runs the chip share; here the reduced config
    stands in for it so the CLI wiring runs on the CPU."""
    monkeypatch.setattr(train, "get_chip_share",
                        lambda arch: get_config(arch).reduced())
    out = tmp_path / "r.json"
    cache_dir = jax.config.jax_compilation_cache_dir
    res = train.main(["--scale", "device", "--steps", "2",
                      "--num-batches", "4", "--byzantine", "1",
                      "--batch", "8", "--seq-len", "32", "--out", str(out)])
    # main(argv) leaves the compile cache to the command-line entry points
    assert jax.config.jax_compilation_cache_dir == cache_dir
    assert res["device"]["platform"] == "cpu"
    assert (res["lr"], res["warmup_steps"]) == (1e-3,
                                                train.DEVICE_WARMUP_STEPS)
    assert res["tokens_per_step"] == 8 * 32
    assert len(res["history"]) == 2
    assert json.loads(out.read_text())["history"] == res["history"]


def test_chip_share_keeps_published_widths():
    full, share = minitron_4b.CONFIG, get_chip_share("minitron-4b")
    changed = {f for f in ("num_layers", "vocab_size", "d_model", "d_ff",
                           "num_heads", "num_kv_heads", "head_dim")
               if getattr(full, f) != getattr(share, f)}
    assert changed == set(minitron_4b.REDUCED)
    assert share.vocab_size * 8 == full.vocab_size
    assert 600e6 < share.param_count() < 700e6


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro import optim
    from repro.configs import get_config
    from repro.core import RobustConfig
    from repro.data.tokens import TokenStream
    from repro.launch import train
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"))
    cfg = get_config("minitron-4b").reduced()
    rc = RobustConfig(num_workers=4, num_byzantine=1, num_batches=4,
                      attack="sign_flip", aggregator="gmom")
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=64,
                         global_batch=8, num_workers=4, seed=0)
    runs = [train.run_device_steps(cfg, rc, optim.adamw(1e-3), stream,
                                   steps=1, seed=0, mesh=mesh,
                                   gather_grads=g) for g in (False, True)]
    for r in runs:
        assert {len(x.sharding.device_set)
                for x in jax.tree.leaves(r["params"])} == {4}
    assert runs[0]["history"] == runs[1]["history"]
    n = sum(int(np.sum(np.asarray(a) != np.asarray(b)))
            for a, b in zip(jax.tree.leaves(runs[0]["params"]),
                            jax.tree.leaves(runs[1]["params"])))
    total = sum(x.size for x in jax.tree.leaves(runs[0]["params"]))
    print("OK", n, total)
""")


def test_mesh_step_shard_local_matches_gathered():
    """On a 2x2 (data, model) mesh the step with shard-local aggregation and
    the one with gathered gradients agree: same losses, and parameters that
    differ in at most a few last bits (the Weiszfeld distances are summed
    in a different order)."""
    res = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT], capture_output=True,
        text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert res.returncode == 0, (res.stdout[-800:], res.stderr[-3000:])
    _, n, total = res.stdout.split()[-3:]
    assert int(n) <= int(total) * 1e-4


def test_chip_smoke_refuses_without_tpu():
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
