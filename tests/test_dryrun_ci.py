"""CI-scale dry-run: the real sharding/lowering pipeline on an 8-virtual-
device mesh in a subprocess (the 512-way flag must not leak into this
process — jax locks device count at first init)."""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.core import RobustConfig
    from repro.launch import mesh as mesh_lib, sharding, steps
    from repro import optim
    from repro.roofline import analysis

    arch = "{arch}"
    kind = "{kind}"
    mesh = mesh_lib.make_debug_mesh(data=2, model=2, pod=2)
    cfg = get_config(arch).reduced()
    with jax.set_mesh(mesh):
        params_s = steps.abstract_params(cfg)
        pshard = sharding.param_shardings(params_s, mesh, cfg)
        if kind == "train":
            shape = InputShape("t", seq_len=64, global_batch=32, kind="train")
            batch = steps.train_batch_struct(cfg, shape, 4)
            rc = RobustConfig(num_workers=4, num_byzantine=1, num_batches=4,
                              attack="sign_flip", gmom_max_iters=4)
            opt = optim.adamw(1e-3)
            opt_s = steps.abstract_opt_state(opt, params_s)
            oshard = sharding.opt_state_shardings(opt_s, params_s, mesh, cfg)
            bshard = sharding.batch_shardings(batch, mesh)
            fn = steps.make_group_train_step(cfg, rc, opt, microbatches=2)
            rep = sharding.replicated(mesh)
            lowered = jax.jit(fn, in_shardings=(pshard, oshard, bshard,
                                                rep, rep),
                              donate_argnums=(0, 1)).lower(
                params_s, opt_s, batch,
                jax.ShapeDtypeStruct((2,), jnp.uint32),
                jax.ShapeDtypeStruct((), jnp.int32))
        else:
            shape = InputShape("d", seq_len=128, global_batch=8,
                               kind="decode")
            tok, pos, state = steps.decode_input_struct(cfg, shape)
            sshard = sharding.decode_state_shardings(state, mesh, cfg, 8)
            bspec = sharding.serve_batch_spec(mesh, 8)
            baxis = bspec[0] if len(bspec) else None
            fn = steps.make_serve_step(cfg)
            lowered = jax.jit(
                fn, in_shardings=(pshard, sshard,
                                  jax.NamedSharding(mesh, P(baxis, None)),
                                  jax.NamedSharding(mesh, P(baxis))),
                donate_argnums=(1,)).lower(params_s, state, tok, pos)
        compiled = lowered.compile()
        cost = analysis.collective_bytes(compiled.as_text())
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes > 0
        print("OK", sum(cost.values()))
""")


@pytest.mark.parametrize("arch,kind", [
    ("minitron-4b", "train"),
    ("granite-moe-1b-a400m", "train"),
    ("rwkv6-7b", "train"),
    ("zamba2-2.7b", "train"),
    ("seamless-m4t-medium", "train"),
    ("internvl2-26b", "train"),
    ("minitron-4b", "decode"),
    ("rwkv6-7b", "decode"),
    ("kimi-k2-1t-a32b", "decode"),
])
def test_debug_mesh_lowering(arch, kind):
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(arch=arch, kind=kind)],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert res.returncode == 0, (res.stdout[-1000:], res.stderr[-3000:])
    assert "OK" in res.stdout
