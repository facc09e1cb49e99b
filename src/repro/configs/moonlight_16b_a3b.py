"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B] — DeepSeek-V3's
architecture (arXiv:2412.19437) at hidden size 2048.

``CONFIG`` is the published model: 27 layers, the first with a dense SwiGLU
of 11264, then 26 expert layers of 64 routed experts of 1408 (top-6,
sigmoid scores with a selection bias, the chosen weights normalised and
scaled by 2.446) beside 2 shared experts; multi-head latent attention with
16 heads, no query compression, a 512-wide latent KV, 128 + 64 query/key
head dims (the 64 rotary dims shared by all heads) and 128 value dims;
rope theta 50000; a 163840-token vocabulary.

``CHIP_SHARE`` is what one TPU v5e chip (16 GB) holds of a robust
data-parallel training deployment: each expert layer's 64 experts spread
over 8 chips (expert parallelism 8), the embedding and the output head
vocab-parallel over 8 chips, and whole layers per pipeline stage.  This
chip holds the first stage: the dense layer and 4 expert layers (one whole
period, the leading dense layer counted once, and the floor of four
following layers), experts 0-7 of each expert layer, and 1/8 of the
vocabulary.  The router keeps its 64 outputs and top-6: a token sent to an
expert held elsewhere gets nothing from it here, in the program and in the
reference alike.  568,484,352 parameters, and 4 x 64 selection biases held
at their initial 0 (a leaf of the parameters that no gradient reaches).

``REDUCED`` lists each key changed from the published config and why.
"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    source="hf:moonshotai/Moonlight-16B-A3B",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,               # one routed expert
    vocab_size=163840,
    rope_theta=5e4,
    norm_eps=1e-5,
    num_experts=64,
    experts_per_token=6,
    moe_router="sigmoid",
    num_shared_experts=2,
    routed_scaling=2.446,
    balance_alpha=1e-4,      # DeepSeek-V3 §4.2; not in the published config
    first_dense_layers=1,
    dense_d_ff=11264,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
)

CHIP_SHARE = CONFIG.with_(name="moonlight-16b-a3b-chip", num_layers=5,
                          experts_held=8, vocab_size=20_480)

REDUCED = {
    "num_layers": "27 -> 5: the first pipeline stage, the dense layer and 4 "
                  "expert layers; the other 22 lie on further stages",
    "experts_held": "64 -> 8: experts 0-7 of each expert layer, this chip's "
                    "share under expert parallelism 8; the router still "
                    "scores all 64 and picks 6",
    "vocab_size": "163840 -> 20480: this chip's slice of a vocab-parallel "
                  "embedding and head over 8 chips; token ids are drawn "
                  "from the slice and the loss is over the slice",
}
