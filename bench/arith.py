"""Operation and byte counts the benchmark's utilisation metrics divide by.

Kept with the benchmark so that a change to the program cannot change the
yardstick.  Every count is the work the algorithm needs, from the shapes a
configuration states; work the program repeats to save memory (remat's
second forward pass) is not counted.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peak table's row for ``device_kind``.  A device that is not in
    the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path}; "
                       f"it has {sorted(table)}")
    return table[device_kind]


def dense_lm_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    attention projections, the three SwiGLU matrices and the output head.
    The embedding is a row lookup and the norms are elementwise."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = d * h * hd * 2 + d * kv * hd * 2
    mlp = 3 * d * f
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def dense_lm_train_flops_per_token(cfg: dict, seq_len: int) -> int:
    """Forward and backward operations per trained token: 6 per matmul
    parameter, plus causal attention's scores and values.  A query at
    position t attends to t + 1 keys, so over a sequence the two (T, T)
    products each cost on average T / 2 keys x 2 flops x heads x head_dim,
    three times over for forward and backward."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    attn = cfg["num_hidden_layers"] * 3 * 2 * seq_len * h * hd
    return 6 * dense_lm_matmul_params(cfg) + attn


def gd_round_bytes(num_workers: int, dim: int, itemsize: int = 4) -> int:
    """HBM traffic a GMoM server round needs: one read of the (m, d) stacked
    reports and one write of the (d,) aggregate.  Everything else the round
    touches (batch means, weights, the Weiszfeld iterate) can stay on chip."""
    return (num_workers * dim + dim) * itemsize


def gd_round_kernel_flops(num_workers: int, dim: int) -> int:
    """Operations the round's batch means need: one multiply and one add per
    report coordinate.  The Weiszfeld iterations on the (k, d) means are
    data-dependent in number and not counted."""
    return 2 * num_workers * dim


def linreg_round_flops(total_samples: int, dim: int) -> int:
    """Operations of one GD round on the paper's linear regression: every
    worker's residual X theta - y and gradient X^T r over its samples, two
    products of N x d multiply-adds."""
    return 4 * total_samples * dim


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The least time a call can take on the chip, and which bound sets it
    (``"compute"`` or ``"memory"``)."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
