"""Kernel tiling (paper §III).

``subkernel_decomposition`` is the paper's kernel-tiling trick: a K x K
kernel with K > native_k is split into ceil(K/3)^2 sub-kernels of at most
3 x 3 taps, each assigned to a different core; the adder trees accumulate
the partial results.  We use the same decomposition arithmetically in
``kernels/ops.py`` for K > 8 (MXU-unfriendly kernels).  Strip, tile and
VMEM planning live in ``core.conv_plan``.
"""

from __future__ import annotations


def subkernel_decomposition(k: int, native_k: int = 3
                            ) -> list[tuple[int, int, int, int]]:
    """Split a K x K kernel into (row_off, col_off, kh, kw) sub-kernels.

    Matches §III: "a 5x5 kernel can be split into four 3x3 sub-kernels" —
    we return the un-padded tap extents (3,3), (3,2), (2,3), (2,2) whose
    union tiles the 5x5; zero-padding to 3x3 is a hardware detail that the
    arithmetic decomposition does not need.
    """
    if k <= native_k:
        return [(0, 0, k, k)]
    subs = []
    for r0 in range(0, k, native_k):
        for c0 in range(0, k, native_k):
            subs.append((r0, c0, min(native_k, k - r0), min(native_k, k - c0)))
    return subs
