"""Operations and bytes that the served work needs. These count what the
algorithm requires, not what the program's layout happens to move: a
decode step reads every weight once, the live keys and values of each
active request once, and writes one new key and value per request; a
prefilled token costs the dense products of every layer (no head: prefill
emits no logits) and causal attention over the positions before it. A
multiply-add is two operations.

An architecture's ``Counts`` (``archs/<name>.py``) works out, from its
shapes, the per-token and per-key quantities this class takes; the
readers (``readers.py``) call the four methods.
"""
from __future__ import annotations


class Counts:
    def __init__(self, *, token_flops: int, head_flops: int,
                 attn_flops_per_key: int, weight_bytes: int,
                 head_bytes: int, kv_bytes_per_token: int):
        self.token_flops = token_flops        # one token through every layer
        self.head_flops = head_flops          # one token's logits
        self._attn = attn_flops_per_key       # one query and key, all layers
        self.layer_weight_bytes = weight_bytes  # every layer's weights
        self.head_bytes = head_bytes
        self.kv_bytes_per_token = kv_bytes_per_token

    def prefill_flops(self, n: int) -> float:
        """Prefill of positions 0..n-1 of one prompt."""
        return self.token_flops * n + self._attn * n * (n + 1) / 2

    def prefill_bytes(self, dispatches: int, valid: int) -> float:
        """Weights once per prefill dispatch, one K/V write per token."""
        return dispatches * self.layer_weight_bytes \
            + valid * self.kv_bytes_per_token

    def decode_flops(self, ctx: int) -> float:
        """One generated token whose query attends ``ctx`` keys."""
        return self.token_flops + self.head_flops + self._attn * ctx

    def decode_step_bytes(self, ctxs) -> float:
        """One decode step over active requests attending ``ctxs`` keys."""
        return self.layer_weight_bytes + self.head_bytes + \
            self.kv_bytes_per_token * (sum(ctxs) + len(ctxs))
