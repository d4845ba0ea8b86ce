"""Wave-aware Token-Splitting (paper §3.1; DESIGN.md §2, packed-axis
split decision in DESIGN.md §6).

The GPU notion of a "wave" (gridDim CTAs / 132 SMs) maps on TPU to the tile
quantization of the token dimension: XLA/Mosaic process the M-dimension of a
GEMM in tiles of `unit` rows (a multiple of the 8-row sublane tile; we default
to 256 which is also what our Pallas kernels use), and a split that turns one
partial tile into two wastes an MXU pass per kernel.

Smart-splitting guarantees:
    ceil(L1/u) + ceil(L2/u) == ceil(L/u)      (no extra waves)
    L1 % u == 0                               (prefix split = full waves only)
    |L1 - L2| minimized subject to the above  (balanced overlap)
and, because ``u`` is chosen as a multiple of the TP degree, both splits stay
divisible by TP so the fused ReduceScatter-RMSNorm-AllGather can tile tokens
across the TP group.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple


def smart_split(n_tokens: int, unit: int) -> Optional[Tuple[int, int]]:
    """Split ``n_tokens`` into (L1, L2) wave-aware halves; None if unsplittable.

    L1 is the prefix split (full waves only); L2 = n - L1 carries the single
    partial wave, exactly matching the paper's 300-CTA -> (132, 168) example
    with unit=132.
    """
    if unit <= 0:
        raise ValueError(f"unit must be positive, got {unit}")
    if n_tokens < 2 * unit:
        return None  # a split would necessarily add a wave (or produce L1=0)
    total_waves = math.ceil(n_tokens / unit)
    l1 = (total_waves // 2) * unit
    l2 = n_tokens - l1
    assert l1 > 0 and l2 > 0
    return l1, l2


def naive_split(n_tokens: int) -> Tuple[int, int]:
    """Equal halves, ignoring wave quantization (paper's strawman)."""
    l1 = n_tokens // 2
    return l1, n_tokens - l1


def wave_count(n_tokens: int, unit: int) -> int:
    return math.ceil(n_tokens / unit)


def split_sizes_for_batch(
    n_tokens: int,
    *,
    unit: int,
    min_tokens: int,
    row_multiple: int = 1,
) -> Optional[Tuple[int, int]]:
    """The split ``split_decision`` picks, without its reason.

    ``row_multiple`` constrains the split point to a multiple of the batch
    size when tokens are laid out (B, S) row-major and we split along S (all
    rows split at the same sequence position, keeping shapes rectangular).
    Returns None when the batch is too small for splitting to pay off
    (paper: TokenWeave is bypassed below ~1K tokens; the fused kernel is
    still used unsplit).
    """
    return split_decision(n_tokens, unit=unit, min_tokens=min_tokens,
                          row_multiple=row_multiple).split


@dataclasses.dataclass(frozen=True)
class SplitDecision:
    """Reasoned split decision (the trace-attribution record's core,
    DESIGN.md §12): the split chosen — or None plus WHY not.

    reasons: ``split`` (weave fires), ``below_min_tokens`` (under the
    paper's ~1K-token bypass threshold), ``below_wave_floor`` (enough
    tokens nominally, but a cut could not avoid adding a wave — fewer
    than two full tile units at the effective quantum)."""
    split: Optional[Tuple[int, int]]
    reason: str
    n_tokens: int
    unit: int                 # effective wave quantum (lcm w/ row_multiple)
    min_tokens: int


def split_decision(
    n_tokens: int,
    *,
    unit: int,
    min_tokens: int,
    row_multiple: int = 1,
) -> SplitDecision:
    """The weave split arithmetic (DESIGN.md §14): the wave-aware halves
    of ``n_tokens``, or None with the reason — the one decision every
    dispatch kind takes (``models.transformer.weave_decision_info`` picks
    its unit and floor), and the record the observability layer
    (DESIGN.md §12) attaches to every forward step."""
    eff_unit = math.lcm(unit, max(row_multiple, 1))
    if n_tokens < min_tokens:
        return SplitDecision(None, "below_min_tokens", n_tokens, eff_unit,
                             min_tokens)
    if n_tokens < 2 * unit:
        return SplitDecision(None, "below_wave_floor", n_tokens, eff_unit,
                             min_tokens)
    split = smart_split(n_tokens, eff_unit)
    return SplitDecision(split, "split" if split is not None
                         else "below_wave_floor", n_tokens, eff_unit,
                         min_tokens)


def packed_split(
    n_tokens: int,
    *,
    unit: int,
    min_tokens: int,
) -> Optional[Tuple[int, int]]:
    """Weave decision for a packed hybrid iteration (DESIGN.md §6): the
    split ``weave_decision_info`` takes at the packed site (DESIGN.md
    §14).

    A packed plan concatenates prefill-chunk segments, single-token decode
    slots, and speculative verify windows along ONE flat token axis, so the
    split point needs no rectangularity constraint (``row_multiple == 1``)
    and — crucially — the decision sees the TRUE combined iteration size.
    Under the two-dispatch scheme each half is judged against
    ``min_tokens`` alone; mixed iterations that would jointly cross the
    threshold fall back to the unsplit path on both calls.  Segment
    boundaries need not align with the split: a segment straddling the cut
    attends the prefix split's freshly written KV (the §3.1 chunked
    attention dependency).
    """
    return split_sizes_for_batch(n_tokens, unit=unit, min_tokens=min_tokens,
                                 row_multiple=1)


def pad_to_multiple(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple
