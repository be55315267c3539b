"""Operations and bytes that the served work needs, from the shapes of a
configuration. These count what the algorithm requires, not what the
program's layout happens to move: a decode step reads every weight once,
the live keys and values of each active request once, and writes one new
key and value per request; a prefilled token costs the dense products of
every layer (no head: prefill emits no logits) and causal attention over
the positions before it. A multiply-add is two operations.
"""
from __future__ import annotations

from weights import dims


class Counts:
    def __init__(self, conf: dict):
        m = dims(conf["config"])
        self.m = m
        d, h, kh, hd, f = m["d"], m["heads"], m["kv_heads"], m["hd"], m["ff"]
        self.layer_params = d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * f
        self.head_params = d * m["vocab"]
        L = m["layers"]
        self.layer_weight_bytes = 2 * L * self.layer_params
        self.head_bytes = 2 * self.head_params
        self.kv_bytes_per_token = 2 * L * kh * hd * 2
        self._attn = 4 * L * h * hd          # operations per attended key

    def prefill_flops(self, n: int) -> float:
        """Prefill of positions 0..n-1 of one prompt."""
        dense = 2 * self.m["layers"] * self.layer_params * n
        return dense + self._attn * n * (n + 1) / 2

    def prefill_bytes(self, dispatches: int, valid: int) -> float:
        """Weights once per prefill dispatch, one K/V write per token."""
        return dispatches * self.layer_weight_bytes \
            + valid * self.kv_bytes_per_token

    def decode_flops(self, ctx: int) -> float:
        """One generated token whose query attends ``ctx`` keys."""
        return 2 * (self.m["layers"] * self.layer_params + self.head_params) \
            + self._attn * ctx

    def decode_step_bytes(self, ctxs) -> float:
        """One decode step over active requests attending ``ctxs`` keys."""
        return self.layer_weight_bytes + self.head_bytes + \
            self.kv_bytes_per_token * (sum(ctxs) + len(ctxs))
