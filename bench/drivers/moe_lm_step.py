"""Driver: the group-mode robust training step of a DeepSeek-V3-style LM
(latent attention, a leading dense layer, shared and routed experts of
which the chip holds a share).

The same run as ``lm_step``'s, from whose pieces it is made: set-up
compiles the program's step (``launch.steps.jit_group_train_step``), makes
the weights and batches on the device from the seed and drives the check
steps; the window drives the same compiled step on, one step in flight
ahead of the host; then the program's state is freed and the plain
reference (``bench/reference/mla_moe_lm.py``) repeats the check steps,
compared by ``lm_step.compare``.  Here the weights give an expert leaf
(layers, experts, fan_in, fan_out) its true fan-in and the selection bias
its 0; the step's router counters are kept for the window; and the share
of the routing choices in which the program's bf16 forward and the f32
reference differ, at the first check step's weights on its first group, is
a reading.

Traffic keys: as ``lm_step``'s, and ``"tokens": "uniform"``: the batches
come from ``bench/gen/uniform_tokens.py`` (why is said there).
"""

from __future__ import annotations

import functools
import gc
import importlib
import math
import time

from bench import arith_moe, harness
from bench.drivers import lm_step
from bench.gen import uniform_tokens
from bench.reference import mla_moe_lm

# configuration keys -> the program's ModelConfig fields
_FIELDS = {"hidden_size": "d_model", "moe_intermediate_size": "d_ff",
           "intermediate_size": "dense_d_ff",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads",
           "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
           "tie_word_embeddings": "tie_embeddings",
           "n_routed_experts": "experts_held",
           "n_routed_experts_published": "num_experts",
           "num_experts_per_tok": "experts_per_token",
           "n_shared_experts": "num_shared_experts",
           "routed_scaling_factor": "routed_scaling",
           "balance_alpha": "balance_alpha",
           "first_k_dense_replace": "first_dense_layers",
           "kv_lora_rank": "kv_lora_rank",
           "qk_nope_head_dim": "qk_nope_head_dim",
           "qk_rope_head_dim": "qk_rope_head_dim",
           "v_head_dim": "v_head_dim"}


def program_config(cj: dict):
    """The program's ModelConfig for this configuration: its published
    config with every size set from the configuration file."""
    import jax.numpy as jnp
    mod, attr = cj["program_config"].split(":")
    base = getattr(importlib.import_module(mod), attr)
    kw = {field: cj[key] for key, field in _FIELDS.items()}
    return base.with_(name=cj["name"], dtype=jnp.dtype(cj["compute_dtype"]),
                      param_dtype=jnp.dtype(cj["param_dtype"]), **kw)


def init_params(key, shapes):
    """``lm_step.init_params``'s weights, with an expert leaf (layers,
    experts, fan_in, fan_out) scaled by its own fan-in and the router's
    selection bias at 0."""
    import jax
    import jax.numpy as jnp
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            out.append(jnp.ones(s.shape, s.dtype))
            continue
        if name.endswith("['router_bias']"):
            out.append(jnp.zeros(s.shape, s.dtype))
            continue
        if name == "['embed']":
            fan_in = 1
        elif "['experts']" in name:             # (layers, experts, fan_in, .)
            fan_in = s.shape[2]
        elif name.startswith(("['layers']", "['dense_layers']")):
            fan_in = s.shape[1]                 # (layers, fan_in, ...)
        else:                                   # the output head (d, vocab)
            fan_in = s.shape[0]
        x = jax.random.truncated_normal(jax.random.fold_in(key, i), -2.0,
                                        2.0, s.shape, jnp.float32)
        out.append((x * fan_in ** -0.5).astype(s.dtype))
    return jax.tree.unflatten(treedef, out)


class Feed(lm_step.Feed):
    """``lm_step.Feed`` with this driver's weights, and its batches from
    ``bench/gen/uniform_tokens.py`` (the traffic's ``"tokens"``)."""

    def __init__(self, cj: dict, tr: dict, seed: int, params_shapes):
        import jax
        super().__init__(cj, tr, seed, params_shapes)
        if tr.get("tokens") != "uniform":
            raise ValueError(f"{tr.get('tokens')!r}: this driver draws its "
                             f"tokens with bench/gen/uniform_tokens.py")
        self.make_params = jax.jit(functools.partial(
            init_params, shapes=params_shapes))
        self.make_batch = jax.jit(functools.partial(
            uniform_tokens.lm_batch, vocab_size=cj["vocab_size"],
            seq_len=tr["seq_len"], groups=tr["groups"],
            per_group=tr["seqs_per_group"]))


def build(cj: dict, tr: dict):
    """``(cfg, rc, optimizer, params shapes)``, as ``lm_step.build``."""
    import jax
    from repro import optim
    from repro.core import RobustConfig
    from repro.models import model as model_lib

    cfg = program_config(cj)
    k, rob, adam = tr["groups"], tr["robust"], tr["adamw"]
    attack_kwargs = (() if rob["attack"] == "none"
                     else (("scale", rob["attack_scale"]),))
    rc = RobustConfig(num_workers=k, num_byzantine=rob["byzantine"],
                      num_batches=k, aggregator=rob["aggregator"],
                      attack=rob["attack"], attack_kwargs=attack_kwargs,
                      trim_multiplier=rob["trim_multiplier"],
                      gmom_max_iters=rob["max_iters"], gmom_tol=rob["tol"])
    optimizer = optim.adamw(
        optim.schedule.linear_warmup(adam["peak_lr"],
                                     warmup_steps=adam["warmup_steps"]),
        b1=adam["b1"], b2=adam["b2"], eps=adam["eps"])
    params_s = jax.eval_shape(lambda kk: model_lib.init(kk, cfg),
                              jax.random.PRNGKey(0))
    return cfg, rc, optimizer, params_s


def make_stepper(compiled, feed: Feed, counters: list):
    """``lm_step.make_stepper``'s step, which also appends each step's
    router counters (``moe_local_assignments``, ``moe_load_max``, on the
    device) to ``counters``."""
    import jax.numpy as jnp

    def step(state, i):
        params, opt_state = state
        with harness.span("bench.make_batch"):
            batch = feed.batch(i)
        with harness.span("bench.dispatch"):
            params, opt_state, metrics = compiled(
                params, opt_state, batch, feed.step_key(i), jnp.int32(i))
        counters.append((metrics["moe_local_assignments"],
                         metrics["moe_load_max"]))
        return (params, opt_state), metrics["loss_mean"]
    return step


def reference_readings(feed: Feed, cj: dict, tr: dict, *,
                       precision: str = "f32",
                       half_batch: bool = False) -> dict:
    """``lm_step.reference_readings`` with this model's reference."""
    n = tr["check_steps"]
    ref = mla_moe_lm.train_steps(
        feed.params(), [feed.batch(i) for i in range(n)],
        [feed.step_key(i) for i in range(n)], cj, tr["robust"], tr["adamw"],
        precision=precision, half_batch=half_batch)
    return {"losses": ref["losses"], "first": ref["first_agg_norms"],
            "change": feed.change_norms(ref["params"])}


def program_routing(cfg, params, tokens):
    """The program's expert choices for (B, T) tokens in its own forward:
    ``(expert layers, B*T, K)`` ids, from its blocks and router."""
    import jax
    from repro.models import attention, blocks, layers, moe

    def choose(x, p):
        h = x + attention.mla_apply(
            p["attn"], blocks.mla_spec(cfg),
            layers.rmsnorm(p["ln_attn"], x, eps=cfg.norm_eps))
        ids, _, _ = moe.deepseek_route(
            p["moe"], blocks.deepseek_moe_spec(cfg),
            layers.rmsnorm(p["ln_mlp"], h, eps=cfg.norm_eps))
        return blocks.deepseek_block(p, cfg, x)[0], ids

    x = params["embed"][tokens].astype(cfg.dtype)
    if "dense_layers" in params:
        x, _ = jax.lax.scan(
            lambda h, p: (blocks.deepseek_block(p, cfg, h)[0], None), x,
            params["dense_layers"])
    _, ids = jax.lax.scan(choose, x, params["layers"])
    return ids


def routing_differs(cfg, cj: dict, feed: Feed) -> float:
    """Share of the (token, expert layer) choices of K experts that the
    program's forward and the f32 reference's make differently, at the
    seed's weights on the first group of the first batch."""
    import jax
    import jax.numpy as jnp
    params = feed.params()
    tokens = feed.batch(0)["tokens"][0]
    prog = jax.jit(functools.partial(program_routing, cfg))(params, tokens)
    cfg_t = tuple(sorted((k, v) for k, v in cj.items()
                         if isinstance(v, (bool, int, float, type(None)))))
    ref = mla_moe_lm.routing(params, tokens, cfg_t)
    e = cj["n_routed_experts_published"]
    ref = ref.reshape(prog.shape)
    same = jnp.sum(jax.nn.one_hot(prog, e).sum(-2)
                   * jax.nn.one_hot(ref, e).sum(-2))
    return 1.0 - float(same) / prog.size


def run(ctx: harness.Context) -> dict:
    import jax

    cj, tr = ctx.config, ctx.traffic
    cfg, rc, optimizer, params_s = build(cj, tr)
    feed = Feed(cj, tr, ctx.seed, params_s)
    tokens_per_step = tr["groups"] * tr["seqs_per_group"] * tr["seq_len"]
    with harness.span("bench.compile"):
        compiled = lm_step.compile_step(cfg, rc, optimizer, params_s, feed)
    compiled_bytes = harness.compiled_bytes(compiled)
    mem = compiled.memory_analysis()
    peak_bytes = getattr(mem, "peak_memory_in_bytes", None)
    ctx.log(f"[moe_lm_step] compiled at {ctx.elapsed():.1f} s, "
            f"{compiled_bytes} B on the device, peak {peak_bytes} B")
    counted = []
    step = make_stepper(compiled, feed, counted)
    state, prog = lm_step.check_steps(step, optimizer, feed, tr)
    prog["local_assignments"] = [int(a) for a, _ in counted]
    n_check = tr["check_steps"]
    setup_s = ctx.elapsed()
    ctx.log(f"[moe_lm_step] set-up {setup_s:.2f} s; check losses "
            f"{prog['losses']}")

    # the window
    losses = []
    counted.clear()
    n_window = tr["trace_steps"] if ctx.trace else None
    with harness.traced(ctx) as trace:
        t0 = time.perf_counter()
        i, prev = n_check, None
        while True:
            state, loss = step(state, i)
            losses.append(loss)
            i += 1
            if prev is not None:
                with harness.span("bench.wait"):
                    prev.block_until_ready()
            prev = loss
            if n_window is not None:
                if len(losses) >= n_window:
                    break
            elif time.perf_counter() - t0 >= ctx.seconds:
                break
        with harness.span("bench.wait"):
            jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    losses = [float(x) for x in losses]
    assignments = sum(int(a) for a, _ in counted)
    load_max = [float(m) for _, m in counted]
    failed = sum(not math.isfinite(x) for x in losses)
    peak = harness.memory_peak_bytes()
    ctx.log(f"[moe_lm_step] window {window_s:.3f} s, {len(losses)} steps, "
            f"last loss {losses[-1]}, {assignments} expert assignments")

    # free the program's state, then the reference
    del state, compiled, step, prev
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(feed, cj, tr)
    differs = routing_differs(cfg, cj, feed)
    ctx.log(f"[moe_lm_step] reference {time.perf_counter() - t_ref:.1f} s; "
            f"losses {ref['losses']}; routing differs on {differs:.2e}")
    checks, gaps = lm_step.compare(prog, ref, tr["limits"])
    moe_calls = (len(losses) * tr["groups"]
                 * (cj["num_hidden_layers"] - cj["first_k_dense_replace"]))
    return {
        "setup_s": setup_s, "attempted": len(losses), "failed": failed,
        "e2e": {"train_tokens_per_s":
                len(losses) * tokens_per_step / window_s},
        "checks": checks, "memory_peak_bytes": peak,
        "trace_path": trace["path"],
        "counters": {"tokens": len(losses) * tokens_per_step,
                     "steps": len(losses), "window_s": window_s,
                     "flops_per_token":
                         arith_moe.moe_lm_train_flops_per_token(
                             cj, tr["seq_len"]),
                     "compiled_bytes": compiled_bytes,
                     "moe_local_assignments": assignments,
                     "moe_load_max": sum(load_max) / len(load_max),
                     "moe_expert_calls": moe_calls},
        "readings": dict(gaps, program=prog, reference=ref,
                         routing_differs=differs,
                         compiled_peak_bytes=peak_bytes,
                         moe_load_max=load_max),
    }
