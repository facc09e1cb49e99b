"""Minitron-4B [arXiv:2407.14679] — width/depth-pruned Nemotron-4.

``CONFIG`` is the published model.  Its MLP departs from it: the dense
family computes a SwiGLU (three d_model x d_ff matrices) where Minitron's
published MLP is squared-ReLU (two), which adds 28.3 M parameters a layer.

``CHIP_SHARE`` is what one TPU v5e chip (16 GB) holds of a training
deployment, the configuration ``launch/train.py --scale device`` runs.
Every width is published: d_model 3072, 24 query heads and 8 KV heads of
128, d_ff 9216.  Deployment it stands for: the embedding and the output head
vocab-parallel over 8 chips, and whole layers per pipeline stage.  So this
chip holds 1/8 of the vocabulary rows and the first few whole layers; the
layers left out lie on further stages.  At ~18 B a parameter (bf16 params,
f32 Adam moments, k=4 bf16 stacked group gradients) the published 2 x
256,000 x 3,072 embedding and head alone would need 28 GB.

``REDUCED`` lists each key changed from the published config and why.
"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="minitron-4b",
    family="dense",
    source="arXiv:2407.14679",
    num_layers=32,
    d_model=3072,
    num_heads=24,
    num_kv_heads=8,
    head_dim=128,
    d_ff=9216,
    vocab_size=256000,
    rope_theta=1e4,
)

CHIP_SHARE = CONFIG.with_(name="minitron-4b-chip", num_layers=4,
                          vocab_size=32_000)

REDUCED = {
    "num_layers": "32 -> 4: whole layers of one pipeline stage; 196.6 M "
                  "embedding/head + 4 x 110.1 M layer parameters = 637 M, "
                  "about 11.5 GB of weights, moments and stacked gradients",
    "vocab_size": "256000 -> 32000: this chip's slice of a vocab-parallel "
                  "embedding and head over 8 chips; token ids are drawn "
                  "from the slice and the loss is over the slice",
}
