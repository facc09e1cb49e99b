"""Layer B, RV204: static VMEM-budget audit for the fused round kernel.

Three invariants, checked without running (or even tracing) the kernel:

1. ``VMEM_BUDGET_BYTES <= DEVICE_VMEM_BYTES`` — the provisioning budget
   must fit the declared per-core capacity.
2. The dispatcher's ``fits_vmem(m, k, d)`` and the kernel's own
   ``_check_vmem`` guard agree on a (m, k, d) grid spanning both sides of
   the budget boundary: ``fits_vmem`` True  ⟺  the guard does not raise,
   with the exact ``extra_bytes`` the round kernel passes.  The two
   formulas live ~40 lines apart and share only by convention — this is
   the drift gate.
3. The paper's own scale fits: m=50 workers, k ∈ {11, 25} batches
   (§4's q=5 / q=12 regimes at 2q+1 resp. the uneven split), d=100 — the
   fused path must cover every configuration the repro actually runs.
"""

from __future__ import annotations

import itertools

from repro.verify.rules import Finding

# grid spanning the budget boundary: with k=64 the resident block crosses
# the budget between d=32768 and d=131072, so both guard outcomes occur.
GRID_M = (8, 50, 128)
GRID_K = (4, 11, 25, 64)
GRID_D = (100, 512, 4096, 7680, 8192, 32768, 131072)

PAPER_SHAPES = ((50, 11, 100), (50, 25, 100))

_PATH = "src/repro/kernels/geomed/round.py"


def _guard_ok(round_mod, m: int, k: int, d: int) -> bool:
    """Does the kernel's own _check_vmem accept this shape (with the exact
    extra_bytes round_aggregate_kernel passes)?"""
    tile_d = round_mod.TILE_D
    d_pad = -(-d // tile_d) * tile_d
    try:
        round_mod._check_vmem(k, d_pad,
                              extra_bytes=round_mod._tile_bytes(m, k, tile_d))
        return True
    except ValueError:
        return False


def check_vmem_budget() -> list[Finding]:
    from repro.kernels.geomed import round as round_mod

    findings: list[Finding] = []
    budget = round_mod.VMEM_BUDGET_BYTES
    device = round_mod.DEVICE_VMEM_BYTES
    if budget > device:
        findings.append(Finding(
            rule="RV204", path=_PATH, line=0, col=0,
            message=f"VMEM_BUDGET_BYTES={budget} exceeds the declared "
                    f"DEVICE_VMEM_BYTES={device}"))

    for m, k, d in itertools.product(GRID_M, GRID_K, GRID_D):
        fits = round_mod.fits_vmem(m, k, d)
        guard = _guard_ok(round_mod, m, k, d)
        if fits != guard:
            findings.append(Finding(
                rule="RV204", path=_PATH, line=0, col=0,
                message=f"fits_vmem and _check_vmem disagree at "
                        f"(m={m}, k={k}, d={d}): dispatcher says "
                        f"{'fits' if fits else 'reject'}, kernel guard "
                        f"says {'fits' if guard else 'reject'} — the two "
                        f"formulas drifted"))

    for m, k, d in PAPER_SHAPES:
        if not round_mod.fits_vmem(m, k, d):
            findings.append(Finding(
                rule="RV204", path=_PATH, line=0, col=0,
                message=f"paper-scale shape (m={m}, k={k}, d={d}) no "
                        f"longer fits the fused-kernel VMEM budget "
                        f"({round_mod.round_resident_bytes(m, k, d)} B > "
                        f"{budget} B)"))
    return findings
