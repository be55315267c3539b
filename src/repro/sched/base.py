"""Scheduler substrate: step composition over the serving engine.

A ``Scheduler`` owns the serving loop's *policy* decisions — which queued
requests are admitted when, and how a single engine step is composed out of
the two phase dispatches (summarization prefill chunks on the NPU path,
generation decode on the PIM path). The engine exposes phase primitives
(``admit_wave`` / ``build_prefill_job`` / ``dispatch_prefill_chunk`` /
``finish_prefill`` / ``dispatch_decode`` / ``resolve_decode``); the
scheduler sequences them.

The contract every policy must honour: **scheduling never changes
numerics**. A request's prefill and greedy decode are slot-local (per-slot
masking in both the chunked flash prefill and the fused decode step), so any
interleaving of waves and chunks yields identical per-request greedy tokens
— only the dispatch schedule (and therefore the PAS command streams a trace
lowers to) differs. Tests assert this equivalence across all policies.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np


@dataclass
class PrefillJob:
    """An in-flight prefill sub-batch: one admission wave's prompt tokens
    laid out for chunked dispatch, indexed by slot; a dispatch takes the
    rows of the requests with tokens in its chunk. ``next_chunk`` advances
    one chunk per ``dispatch_prefill_chunk`` call, so a scheduler can
    spread a wave's summarization work across engine steps (NeuPIMs-style
    sub-batch interleaving) instead of running it to completion."""
    wave: List[Tuple[int, object]]      # [(slot, Request), ...]
    tokens: np.ndarray                  # (B, n_chunks * chunk) int32, by slot
    valid: np.ndarray                   # (B, n_chunks * chunk) bool, by slot
    chunk: int
    n_chunks: int
    sub_batch: int                      # wave ordinal (trace sub-batch id)
    next_chunk: int = 0
    _wave_taken: bool = False

    @property
    def done(self) -> bool:
        return self.next_chunk >= self.n_chunks

    def next_valid_count(self) -> int:
        """Valid prompt tokens in the chunk the next dispatch would run —
        what a mapping-aware policy routes on."""
        if self.done:
            return 0
        c, C = self.next_chunk, self.chunk
        return int(self.valid[:, c * C:(c + 1) * C].sum())

    def take_completed(self) -> List[Tuple[int, object]]:
        """(slot, req) pairs whose prefill finished since the last call.
        The unpacked layout fills the wave's rows chunk by chunk, so the
        whole wave completes with the final chunk; ``PackedPrefillJob``
        overrides this with per-dispatch completions."""
        if self.done and not self._wave_taken:
            self._wave_taken = True
            return list(self.wave)
        return []


class Scheduler:
    """Base policy. ``step(engine)`` composes one engine step and returns
    the decode tokens emitted (same contract as ``ServeEngine.step``)."""

    name = "base"

    def __init__(self):
        self.stats: Dict[str, int] = {
            "steps": 0,          # scheduler steps taken
            "overlapped": 0,     # prefill chunk co-scheduled with decode
                                 # (two dispatches, scored concurrent)
            "fused": 0,          # overlapped step lowered as ONE dispatch
            "superstep": 0,      # multi-step decode dispatch (k steps/fetch)
            "serialized": 0,     # both phases present, run back-to-back
            "prefill_only": 0,   # prefill chunk, no resident decode batch
            "decode_only": 0,    # decode only
            "idle": 0,           # nothing to do (open-loop clock tick)
        }
        self.kind: Optional[str] = None   # the last step's kind

    def step(self, engine) -> List[Tuple[int, int]]:
        raise NotImplementedError

    def _tick(self, kind: str) -> None:
        self.stats["steps"] += 1
        self.stats[kind] += 1
        self.kind = kind
