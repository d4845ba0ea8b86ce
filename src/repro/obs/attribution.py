"""Per-forward weave-decision attribution (DESIGN.md §12).

Every model dispatch the engine runs gets one ``WeaveAttribution``
record: what the weave decision saw (tokens, threshold, wave unit), what
it chose (split + reason, straight from
``models.transformer.weave_decision_info`` — the SAME decision object
that increments ``EngineStats.weave_forwards``, so trace-derived weave
rates match the counter exactly), and what that choice is worth — the
§9 two-stream sim roofline's estimate of compute / comm / overlapped
virtual time for this forward (``sim.overlap_sim.step_attribution``).

The ``Attributor`` prices with ``HW(tile=pcfg.split_unit_for(tp))`` so
the sim's split decisions quantize at the same wave unit the engine
actually uses, and memoizes by (mode, tokens): a steady decode loop
prices each distinct batch size once.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models.transformer import WeaveInfo
from repro.sim.overlap_sim import HW, step_attribution


@dataclasses.dataclass(frozen=True)
class WeaveAttribution:
    """One forward step's weave decision + estimated time breakdown."""
    kind: str            # prefill | decode | verify | packed
    b: int
    s: int
    tokens_real: int     # non-pad tokens committed by this forward
    tokens_static: int   # b * s — what the split decision saw
    weave: bool
    reason: str          # split | below_min_tokens | below_wave_floor |
    #                      weave_disabled | paged_pool_unsplit
    split: Optional[Tuple[int, int]]
    method: str          # tokenweave | ringweave | ring | fuseonly |
    #                      reordered | vanilla
    threshold: int
    unit: int
    est_compute: float
    est_comm: float
    est_overlapped: float
    est_makespan: float

    def args(self) -> dict:
        """JSON-able Chrome-trace ``args`` payload; carries every field
        ``validate_chrome_trace`` requires of a forward span."""
        return {
            "kind": self.kind,
            "weave": self.weave,
            "reason": self.reason,
            "tokens": self.tokens_static,
            "tokens_real": self.tokens_real,
            "threshold": self.threshold,
            "split": list(self.split) if self.split else None,
            "method": self.method,
            "est_compute": round(self.est_compute, 9),
            "est_comm": round(self.est_comm, 9),
            "est_overlapped": round(self.est_overlapped, 9),
        }


class Attributor:
    """Prices forward steps on the §9 sim roofline for trace spans."""

    def __init__(self, cfg: ModelConfig, pcfg: ParallelConfig, tp: int):
        self.cfg = cfg
        self.pcfg = pcfg
        self.tp = max(int(tp), 1)
        self.hw = HW(tile=pcfg.split_unit_for(self.tp))
        self._cache: Dict[Tuple, Dict[str, float]] = {}

    def price(self, mode: str, tokens: int) -> Dict[str, float]:
        key = (mode, tokens)
        got = self._cache.get(key)
        if got is None:
            got = self._cache[key] = step_attribution(
                self.cfg, mode, max(tokens, 1), tp=self.tp, hw=self.hw)
        return got

    def attribute(self, info: WeaveInfo, *, b: int, s: int, n_real: int,
                  kind: str) -> WeaveAttribution:
        if info.weave:
            method = "tokenweave"
        else:
            method = {"fused": "fuseonly",
                      "reordered": "reordered"}.get(self.pcfg.comm_mode,
                                                    "vanilla")
        est = self.price(method, b * s)
        return WeaveAttribution(
            kind=kind, b=b, s=s, tokens_real=n_real, tokens_static=b * s,
            weave=info.weave, reason=info.reason, split=info.split,
            method=method, threshold=info.threshold, unit=info.unit,
            est_compute=est["compute"], est_comm=est["comm"],
            est_overlapped=est["overlapped"], est_makespan=est["makespan"])
