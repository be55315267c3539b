"""Serving engine: phase-separated continuous batching (paper §3).

The engine is the TPU realization of the paper's two-phase inference flow:
  * summarization (prefill) — compute-bound: admitted prompts run as whole
    chunks through the flash-attention path (``T.prefill_chunk``), filling
    their slots' KV cache in O(ceil(S/chunk)) dispatches instead of S
    teacher-forced decode steps; a dispatch carries only the rows of the
    requests with tokens in its chunk, each row naming its slot, as many
    as fill ``PREFILL_ROW_TOKENS`` (one 256-token row; every slot at short
    chunks);
  * generation (decode) — bandwidth-bound: one jit'd fused
    decode+sample+terminate dispatch across all active slots per emitted
    token; the only host sync is fetching the (token, done, len) triple —
    and that fetch copies asynchronously while the step's remaining
    dispatches are issued (double-buffered fetch);
  * PAS (core/pas.py) routes the FC work per step and per phase: below the
    MXU token parallelism the GEMV/streaming path wins (generation), above
    it the GEMM path wins (summarization) — every dispatch's phase and
    ``route_fc_tpu`` decision rides its trace event (``route``), the
    Algorithm-1 twin.

Step composition is owned by a ``repro.sched`` policy (``ServeConfig.
policy``): the engine exposes phase primitives — ``admit_wave``,
``build_prefill_job`` / ``dispatch_prefill_chunk`` / ``finish_prefill`` for
summarization, ``dispatch_decode`` / ``resolve_decode`` for generation —
and the scheduler sequences them. ``serial`` reproduces the historical
run-prefill-to-completion wave loop; ``interleaved`` / ``pim_aware``
co-schedule a prefill chunk with the resident batch's decode step so the
NPU-side prefill GEMMs overlap the PIM-side FC mat-vecs (see repro/sched/).

Continuous batching: requests join/leave slots between decode steps; the
batch shape stays static (jit-stable), empty slots are masked. Slot lengths,
last-token state, per-slot generation budgets and termination all live on
device; sampling and the length/termination update are folded into the
jitted decode step. A slot being prefilled across steps is *resident but
not ready* (``slot_ready``): the decode active mask excludes it until its
prompt is fully cached.

Admission is length-bucketed by default: the queue is kept stably sorted by
prefill chunk count, so each admission wave prefills prompts of similar
length and the per-wave chunk loop is not stretched to the longest prompt of
an arbitrary FIFO mix (``ServeConfig.admission = "fifo"`` restores arrival
order; per-request greedy output is identical either way, only the dispatch
schedule changes).

Packed prefill (``ServeConfig.pack``): a wave's prompts are first-fit-
decreasing packed into chunk *lanes* (several short prompts — or a long
prompt's tail plus shorts — per row; repro/sched/packing.py), the dispatch
grid shrinks to the lanes used, and the segment-masked kernel keeps the
packing numerically invisible. Slots arm for generation as soon as their
own prompt's last segment is cached (``PrefillJob.take_completed``), so
short prompts in a packed wave start decoding before the wave drains.

Fused serving steps (``ServeConfig.fuse`` / ``ServeConfig.superstep``): an
overlapped step can be lowered into ONE jitted program carrying both the
prefill chunk and the resident batch's decode (``dispatch_fused_step``), so
the co-issue the simulator scores is what the hardware actually runs; and
when no prefill work is pending, up to ``superstep`` decode steps run
inside one dispatch (``dispatch_decode_superstep``: ``lax.scan`` with
on-device sampling/termination, finished lanes frozen) resolving one host
fetch per superstep instead of per token. Greedy tokens are identical
across all of fused/unfused and superstep in {1, k} — only the dispatch
schedule changes.

A ``repro.trace.TraceRecorder`` can be attached at construction to capture
every request / admission / prefill-dispatch / decode-step / completion
event — including each step's sub-batch membership, overlap/fused flags and
superstep spans — for offline lowering to PAS command streams (see
repro/trace/).

Wall-clock spans (``repro.obs.spans``) are always on: every step leaves a
record of its host time by phase (``serve.admit``, ``serve.prefill``,
``serve.decode``, ``serve.fetch``, ...), its GC pauses and compiles in
``ServeEngine.spans``, and the same names land in a profiler trace.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import flatten_tree as _flatten_cache
from repro.checkpoint.store import unflatten_into as _unflatten_cache
from repro.configs.base import ModelConfig
from repro.core.pas import phase_log_entry
from repro.models import transformer as T
from repro.models.params import init_params
from repro.obs.spans import StepLog, span
from repro.sched import (PackedPrefillJob, PrefillJob, make_scheduler,
                         plan_packed_job)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int = 32
    generated: List[int] = field(default_factory=list)
    done: bool = False
    deferred: int = 0             # admission waves this request was passed over
    gid: Optional[int] = None     # fleet-global id (chaos/snapshot identity)
    # KV-snapshot failover (repro.chaos.snapshots): positions
    # [0, prefill_start) of this prompt are restored from a checkpointed
    # prefix at admission instead of being re-prefilled; ``restore`` holds
    # the pending snapshot payload until ``admit_wave`` scatters it.
    prefill_start: int = 0
    restore: Optional[dict] = None
    # lifecycle on the host clock (time.perf_counter seconds): queued,
    # admitted into a slot, first token resolved, last token resolved
    t_enqueued: Optional[float] = None
    t_admitted: Optional[float] = None
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None


# Prompt tokens one prefill dispatch should carry. A bf16 matmul over M
# token rows does M FLOP per weight byte; the v5e ridge is 197e12 / 819e9
# ~ 240 FLOP/B, so one row of a 256-token chunk already keeps the MXU as
# busy as sixteen, and more rows per dispatch buy at most one re-read of
# the weights. A dispatch carries ceil(256 / prefill_chunk) rows, at most
# one per slot.
PREFILL_ROW_TOKENS = 256


def prefill_rows(max_slots: int, chunk: int) -> int:
    """Rows per unpacked prefill dispatch: enough to reach
    ``PREFILL_ROW_TOKENS``, never more than the slots."""
    return min(max_slots, -(-PREFILL_ROW_TOKENS // chunk))


# Jitted entry points are cached at module level keyed by the (frozen,
# hashable) ModelConfig: every ServeEngine for the same config shares one
# compiled decode step and one compiled prefill per chunk index, instead of
# recompiling per engine instance. Every program that takes the KV cache
# donates it (``donate_argnums`` names the cache's position): the program
# writes its rows into the same buffer, the engine rebinds ``self.cache`` to
# the result, and the old handle is dead — so no dispatch copies the cache.
@functools.lru_cache(maxsize=None)
def _jit_decode(cfg: ModelConfig):
    return jax.jit(functools.partial(T.decode_step, cfg), donate_argnums=2)


@functools.lru_cache(maxsize=None)
def _jit_prefill(cfg: ModelConfig, offset: int):
    """One jitted prefill per chunk offset. Called with four arguments it
    runs the slot grid; with a fifth, ``row_slot``, the row grid."""
    return jax.jit(functools.partial(T.prefill_chunk, cfg, offset=offset),
                   donate_argnums=2)


@functools.lru_cache(maxsize=None)
def _jit_prefill_packed(cfg: ModelConfig, prefix_span: int):
    """One jitted packed prefill per padded prefix span (a chunk multiple);
    jax.jit additionally specializes per row-count shape inside each entry.
    Segment layout, positions and prefix extents are dynamic operands, so a
    serve compiles at most max_slots * max_len/chunk packed variants — the
    same order as the unpacked path's per-chunk-offset jits."""
    return jax.jit(functools.partial(T.prefill_chunk_packed, cfg,
                                     prefix_span=prefix_span),
                   donate_argnums=2)


@functools.lru_cache(maxsize=None)
def _jit_decode_sample(cfg: ModelConfig, temperature: float,
                       eos_token: Optional[int], max_len: int):
    """Fused generation step (``T.decode_and_sample``): decode + sample +
    length/termination update in ONE dispatch, one (3, B) fetch."""
    return jax.jit(functools.partial(
        T.decode_and_sample, cfg, temperature=temperature,
        eos_token=eos_token, max_len=max_len), donate_argnums=1)


@functools.lru_cache(maxsize=None)
def _jit_decode_superstep(cfg: ModelConfig, temperature: float,
                          eos_token: Optional[int], max_len: int, k: int):
    """k generation steps under one jit (``T.decode_superstep``): one
    dispatch and ONE (k, 3, B) host fetch per superstep — the dispatch-
    amortization lever for launch-overhead-bound decode."""
    return jax.jit(functools.partial(
        T.decode_superstep, cfg, k=k, temperature=temperature,
        eos_token=eos_token, max_len=max_len), donate_argnums=1)


@functools.lru_cache(maxsize=None)
def _jit_fused_step(cfg: ModelConfig, temperature: float,
                    eos_token: Optional[int], max_len: int, offset: int):
    """One jitted FUSED overlapped step per static chunk offset: the
    resident batch's decode + the chunk's prefill in one program."""
    return jax.jit(functools.partial(
        T.fused_step, cfg, offset=offset, temperature=temperature,
        eos_token=eos_token, max_len=max_len), donate_argnums=1)


@functools.lru_cache(maxsize=None)
def _jit_fused_step_packed(cfg: ModelConfig, temperature: float,
                           eos_token: Optional[int], max_len: int,
                           prefix_span: int):
    """Fused overlapped step, packed-prefill variant (static prefix span,
    same specialization scheme as ``_jit_prefill_packed``)."""
    return jax.jit(functools.partial(
        T.fused_step_packed, cfg, prefix_span=prefix_span,
        temperature=temperature, eos_token=eos_token, max_len=max_len),
        donate_argnums=1)


@functools.partial(jax.jit, donate_argnums=0)
def reset_slots(cache: dict, slots: jax.Array) -> dict:
    """Zero the admitted slots' cache rows, in place. ``slots`` is a
    fixed-shape (max_slots,) int32 array, the admitted slots first and the
    rest padded with max_slots, so one program serves every wave size and
    only the admitted rows are written (a scatter would visit the padding
    too). A named function, not a partial: its program is
    ``jit_reset_slots``, never taken for a step program."""
    def zero(i, cache):
        return jax.tree.map(lambda leaf: jax.lax.dynamic_update_index_in_dim(
            leaf, jnp.zeros(leaf.shape[:1] + leaf.shape[2:], leaf.dtype),
            slots[i], 1), cache)
    return jax.lax.fori_loop(0, jnp.sum(slots < slots.shape[0]), zero, cache)


@dataclass(frozen=True)
class ServeConfig:
    max_slots: int = 4
    max_len: int = 256
    temperature: float = 0.0      # 0 = greedy
    eos_token: Optional[int] = None
    seed: int = 0
    prefill_chunk: int = 32       # summarization chunk (tokens per dispatch)
    prefill_mode: str = "batched"  # "batched" | "sequential" (reference)
    admission: str = "bucketed"   # "bucketed" (length-sorted) | "fifo"
    # step-composition policy (repro.sched): "serial" | "interleaved" |
    # "pim_aware"; sub_batch caps slots per interleaved admission wave
    # (0 = all free slots); map_dims overrides the (d_model, d_ff) the
    # pim_aware mapping check routes on (smoke engines pass full-model dims).
    policy: str = "serial"
    sub_batch: int = 0
    map_dims: Optional[Tuple[int, int]] = None
    # double-buffered token fetch: start the decode result's device->host
    # copy asynchronously at dispatch so the step's co-scheduled prefill
    # chunk (and host bookkeeping) overlaps the transfer.
    double_buffer: bool = True
    # packed prefill: first-fit-decreasing pack several short prompts (or a
    # long prompt's tail plus short prompts) into each chunk row, so the
    # per-dispatch valid-token fraction stays near 1 on mixed workloads
    # (repro/sched/packing.py; batched prefill path only).
    pack: bool = False
    # how many PrefillJobs an interleaving scheduler keeps in flight over
    # disjoint slots (round-robin chunk dispatch); >1 keeps the NPU prefill
    # stream saturated under bursty arrivals.
    max_prefill_jobs: int = 1
    # decode-occupancy guard: during interleaved steps with a prefill chunk
    # to dispatch, defer the decode by one step when fewer than this many
    # slots are decode-ready, batching it with the next step's decode
    # (0 = disabled; engine.decode_deferrals counts deferrals).
    decode_floor: int = 0
    # fused overlapped step: lower a co-scheduled prefill chunk AND the
    # resident batch's decode into ONE jitted dispatch (T.fused_step), so
    # the NPU/PIM overlap the replay scores actually exists on hardware
    # instead of two back-to-back dispatches (interleaving policies,
    # batched prefill path only; tokens identical either way).
    fuse: bool = False
    # decode supersteps: when no prefill work is pending, run up to this
    # many decode steps inside one dispatch (lax.scan with on-device
    # sampling/termination; finished lanes freeze) and resolve ONE host
    # fetch per superstep. Schedulers cap the step length via
    # choose_superstep so admission latency stays bounded (1 = disabled).
    superstep: int = 1
    # admission-queue capacity: ``add_request`` raises AdmissionRejected
    # once this many requests are already queued (0 = unbounded). Open-loop
    # drivers re-inject rejected arrivals with backoff instead of losing
    # them (trace/arrivals.drive, chaos replayer queue_reject faults).
    queue_cap: int = 0


class AdmissionRejected(RuntimeError):
    """The admission queue is at capacity; the arrival was NOT enqueued.
    Callers own the retry (backoff re-injection) or the terminal-reject
    record — a rejected request is never silently dropped."""


@dataclass
class PendingDecode:
    """A dispatched-but-unresolved decode step: the device fetch array plus
    the host-side view needed to attribute its results at resolve time.
    ``fused`` marks a single-dispatch overlapped step (the decode rode the
    same program as a prefill chunk)."""
    fetch: jax.Array
    active_np: np.ndarray
    n_tok: int
    route: dict
    overlap: bool = False
    fused: bool = False


@dataclass
class PendingSuperstep:
    """A dispatched-but-unresolved decode SUPERSTEP: one (k, 3, B) fetch
    covering k generation steps. ``sid`` is the superstep dispatch ordinal
    (trace consumers group the k per-step events it expands into)."""
    fetch: jax.Array
    active_np: np.ndarray
    k: int
    route: dict
    sid: int


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params,
                 scfg: ServeConfig = ServeConfig(), recorder=None):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        B, L = scfg.max_slots, scfg.max_len
        self.cache = init_params(T.cache_defs(cfg, B, L),
                                 jax.random.PRNGKey(0))
        self.lens = jnp.zeros((B,), jnp.int32)       # device (decode input)
        self.last_tok = jnp.zeros((B,), jnp.int32)   # device (next decode input)
        self.gen_count = jnp.zeros((B,), jnp.int32)  # device (termination)
        self.max_new = jnp.zeros((B,), jnp.int32)    # device (termination)
        self.slot_req: List[Optional[Request]] = [None] * B
        self.slot_ready: List[bool] = [False] * B    # prompt fully prefilled
        self.queue: List[Request] = []
        self._next_rid = 0
        self._rng = jax.random.PRNGKey(scfg.seed)
        self._decode = _jit_decode(cfg)
        self._decode_sample = _jit_decode_sample(
            cfg, scfg.temperature, scfg.eos_token, scfg.max_len)
        self._batched_ok = T.supports_batched_prefill(cfg)
        self._prefill_rows = prefill_rows(B, scfg.prefill_chunk)
        self.scheduler = make_scheduler(self.effective_policy,
                                        sub_batch=scfg.sub_batch,
                                        map_dims=scfg.map_dims,
                                        max_jobs=scfg.max_prefill_jobs,
                                        decode_floor=scfg.decode_floor)
        self.spans = StepLog()        # per-step host spans (repro.obs)
        # dispatch accounting (benchmarks/serve_prefill.py + serve_decode.py
        # read this): "fused" counts single-dispatch overlapped steps (one
        # program carrying a prefill chunk AND a decode — neither bucket
        # alone); a decode superstep counts ONE "decode" dispatch.
        self.dispatch_counts = {"prefill": 0, "decode": 0, "fused": 0}
        self.host_syncs = 0           # blocking device->host transfers
        self.async_fetches = 0        # fetches whose copy started at dispatch
        self.decode_deferrals = 0     # decode dispatches pushed one step by
                                      # the occupancy guard (decode_floor)
        self.superstep_tokens = 0     # decode rounds resolved via supersteps
        self._superstep_seq = 0       # superstep dispatch ordinal (trace)
        # padding-waste accounting for the batched prefill path:
        # token_slots = R*C tokens computed per dispatch of R rows; valid =
        # useful ones;
        # kv_cells = attended KV cells per computed row summed over prefill
        # dispatches (rows * attended span) — what the per-lane prefix-span
        # segregation in the packing planner reduces
        self.prefill_stats = {"token_slots": 0, "valid_tokens": 0,
                              "kv_cells": 0}
        # KV-snapshot accounting (repro.chaos.snapshots). Export transfers
        # are deliberately NOT counted in ``host_syncs``: that counter is
        # the serving protocol's per-step fetch budget (one blocking sync
        # per resolved decode/superstep, linted by repro.verify.protocol);
        # snapshotting is a fleet-clock side channel with its own budget.
        self.snapshot_stats = {"exports": 0, "export_bytes": 0,
                               "export_syncs": 0, "restores": 0,
                               "restored_tokens": 0, "restore_bytes": 0}
        # per-slot row slices rely on every cache leaf carrying the slot
        # axis at position 1 and the kv_seq axis at position 2 (attention
        # K/V + int8 scales do; SSM/RWKV/enc-dec state trees do not)
        self._snapshot_ok = self._batched_ok and all(
            getattr(leaf, "ndim", 0) in (4, 5)
            and leaf.shape[1] == B and leaf.shape[2] == L
            for leaf in jax.tree.leaves(self.cache))
        self.step_idx = 0             # engine step counter (trace timeline)
        self.wave_count = 0           # admission waves (trace sub-batch ids)
        # chaos state (repro.chaos): a degraded engine serves NPU-only
        # (every route forced to the MU/GEMM path — the PIM side is out);
        # a halted engine crashed and must never step or complete again.
        self.degraded = False
        self.halted = False
        self.admission_rejects = 0    # arrivals bounced off a full queue
        self.recorder = recorder
        if recorder is not None:
            recorder.bind(self)

    # ---- request lifecycle ------------------------------------------------- #
    def add_request(self, prompt_tokens, max_new_tokens: int = 32,
                    arrival_step: Optional[int] = None,
                    gid: Optional[int] = None,
                    restore: Optional[dict] = None) -> int:
        """Queue a request. ``arrival_step`` is the TRUE open-loop arrival
        tick when it differs from the current engine clock: a decode
        superstep advances ``step_idx`` k ticks inside one dispatch, so an
        arrival landing mid-span can only be injected at the span boundary
        — the recorded ``arrival_offset`` (schema v5) preserves the real
        arrival so TTFT/queue-wait metrics don't see arrivals batched at
        superstep boundaries.

        ``restore`` attaches a KV-snapshot payload (``prefix_len``,
        ``cache`` rows [0, prefix_len), ``bytes``, ``snapshot_step``): the
        request admits normally, ``admit_wave`` scatters the checkpointed
        prefix into its slot (``import_kv_snapshot``), and prefill then
        covers only positions [prefix_len, len(prompt)-1)."""
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) > self.scfg.max_len - 1:
            raise ValueError(f"prompt ({len(prompt)} tokens) exceeds "
                             f"max_len-1 ({self.scfg.max_len - 1})")
        if self.halted:
            raise RuntimeError("engine is halted (crashed node)")
        if restore is not None:
            if not self.snapshot_supported:
                raise ValueError("KV-snapshot restore needs the batched "
                                 "attention prefill path")
            P = int(restore["prefix_len"])
            if not 0 < P <= len(prompt) - 1:
                raise ValueError(f"restore prefix_len {P} outside "
                                 f"(0, {len(prompt) - 1}]")
        if 0 < self.scfg.queue_cap <= len(self.queue):
            self.admission_rejects += 1
            raise AdmissionRejected(
                f"admission queue at capacity ({self.scfg.queue_cap})")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, max_new_tokens, gid=gid,
                      t_enqueued=time.perf_counter())
        if restore is not None:
            req.prefill_start = int(restore["prefix_len"])
            req.restore = restore
        self.queue.append(req)
        if self.recorder is not None:
            offset = 0 if arrival_step is None \
                else max(self.step_idx - arrival_step, 0)
            self.recorder.on_request(self.step_idx, rid, len(prompt),
                                     max_new_tokens, arrival_offset=offset,
                                     gid=gid)
        return rid

    # ---- chaos hooks (repro.chaos) ----------------------------------------- #
    def set_degraded(self, flag: bool) -> None:
        """PIM-degraded mode: while set, every routing decision this engine
        records (``phase_log_entry`` → trace route dicts, and the
        pim_aware overlap gate) is forced to the NPU/MU path — the node
        keeps serving on normal memory accesses only, it just loses the
        GEMV/PIM side of the crossover. Numerics are untouched: the route
        is a mapping *record*, so greedy tokens stay identical."""
        self.degraded = bool(flag)

    def halt(self) -> None:
        """Crash this engine: it must never dispatch, complete, or accept a
        request again (the chaos replayer recovers its in-flight work onto
        surviving nodes). Host state is left intact for post-mortem reads —
        ``export_recovery_state`` still works on a halted engine."""
        self.halted = True

    def export_recovery_state(self) -> List[dict]:
        """Per-request recovery state for every in-flight request (queued +
        resident, completed ones excluded), from host state only: the
        prompt, the remaining generation budget, and the tokens generated
        so far — exactly what a surviving node needs to re-prefill
        prompt+prefix and continue the greedy stream bit-identically."""
        out = []
        for req in self.queue:
            out.append({"rid": req.rid, "prompt": req.prompt,
                        "max_new": req.max_new_tokens,
                        "generated": list(req.generated),
                        "resident": False, "slot": None})
        for slot, req in enumerate(self.slot_req):
            if req is not None and not req.done:
                out.append({"rid": req.rid, "prompt": req.prompt,
                            "max_new": req.max_new_tokens,
                            "generated": list(req.generated),
                            "resident": True, "slot": slot})
        return sorted(out, key=lambda d: d["rid"])

    # ---- incremental KV snapshots (repro.chaos.snapshots) ------------------- #
    @property
    def snapshot_supported(self) -> bool:
        """KV export/import works when every cache leaf is an attention
        K/V (or int8 scale) tensor with the slot axis at position 1 and the
        kv_seq axis at position 2 — the per-slot row slice both directions
        rely on. SSM/RWKV/enc-dec state trees (and the sequential prefill
        fallback) are not snapshotable."""
        return self._snapshot_ok

    def export_kv_snapshot(self, since: Optional[Dict[int, int]] = None
                           ) -> List[dict]:
        """Export the DELTA of every ready slot's KV state since the last
        snapshot. ``since`` maps gid -> already-snapshotted prefix length
        (the ``SnapshotStore``'s high-water view for this node); a slot
        whose prefix hasn't grown exports nothing. Each entry carries the
        new cache rows [base, prefix_len) per leaf (slot axis removed; the
        kv_seq axis becomes axis 1) plus the host-side request state a
        survivor needs: generated tokens, remaining budget, last token and
        the engine rng — metadata only, never imported into a survivor.
        ``prefix_len`` is host-derived (``len(prompt)-1+len(generated)`` ==
        the slot's device cursor for a ready slot), so the only device
        traffic is the row copies themselves (counted in
        ``snapshot_stats``, not ``host_syncs``)."""
        if not self._snapshot_ok:
            return []
        since = since or {}
        entries: List[dict] = []
        flat = _flatten_cache(self.cache)
        for slot, req in enumerate(self.slot_req):
            if req is None or req.done or not self.slot_ready[slot] \
                    or req.gid is None:
                continue
            P = len(req.prompt) - 1 + len(req.generated)
            base = int(since.get(req.gid, 0))
            if P <= base:
                continue
            idx = (slice(None), slot, slice(base, P))
            rows = {k: np.asarray(leaf[idx]) for k, leaf in flat.items()}
            nbytes = int(sum(a.nbytes for a in rows.values()))
            self.snapshot_stats["exports"] += 1
            self.snapshot_stats["export_bytes"] += nbytes
            self.snapshot_stats["export_syncs"] += len(rows)
            last = int(req.generated[-1]) if req.generated \
                else int(req.prompt[-1])
            entries.append({
                "gid": req.gid, "rid": req.rid, "slot": slot,
                "base": base, "prefix_len": P, "bytes": nbytes,
                "cache": rows,
                "plen": int(len(req.prompt)),
                "generated": list(req.generated),
                "max_new": req.max_new_tokens, "last_tok": last,
                "lens": P, "rng": np.asarray(self._rng).tolist(),
            })
        return entries

    def import_kv_snapshot(self, slot: int, snapshot: dict, *,
                           gid: Optional[int] = None,
                           rid: Optional[int] = None) -> None:
        """Scatter a checkpointed KV prefix into ``slot``: rows
        [0, prefix_len) of every cache leaf are overwritten with the
        snapshot's (merged) rows. Called by ``admit_wave`` for requests
        queued with ``restore=``; the suffix prefill and all decode writes
        land strictly above ``prefix_len``, so the restored rows are
        byte-identical to what a from-zero re-prefill would recompute."""
        P = int(snapshot["prefix_len"])
        rows = snapshot["cache"]
        flat = _flatten_cache(self.cache)
        idx = (slice(None), slot, slice(0, P))
        out = {}
        for key, leaf in flat.items():
            out[key] = leaf.at[idx].set(jnp.asarray(rows[key]))
        self.cache = _unflatten_cache(self.cache, out)
        nbytes = int(snapshot.get("bytes", 0))
        self.snapshot_stats["restores"] += 1
        self.snapshot_stats["restored_tokens"] += P
        self.snapshot_stats["restore_bytes"] += nbytes
        if self.recorder is not None:
            self.recorder.on_restore(
                self.step_idx, gid=gid, rid=rid, prefix_len=P,
                nbytes=nbytes,
                snapshot_step=int(snapshot.get("snapshot_step", -1)))

    def load_stats(self) -> Dict[str, int]:
        """Router hook (``repro.fleet``): the engine's instantaneous load,
        from host state only — queue depth plus slot occupancy is what a
        least-loaded balancer steers on."""
        busy = sum(r is not None for r in self.slot_req)
        return {"queued": len(self.queue), "busy": busy,
                "ready": sum(self.slot_ready),
                "free": self.scfg.max_slots - busy}

    def free_slot_ids(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def ready_slot_ids(self) -> List[int]:
        """Slots with a fully prefilled request — the decode-eligible batch
        (a slot mid-prefill is occupied but not ready)."""
        return [i for i, r in enumerate(self.slot_req)
                if r is not None and self.slot_ready[i]]

    def has_ready_slots(self) -> bool:
        return bool(self.ready_slot_ids())

    @property
    def effective_prefill_mode(self) -> str:
        """What prefill actually runs: "batched" only when both requested
        and supported by the architecture (SSM/hybrid/encdec fall back)."""
        if self._batched_ok and self.scfg.prefill_mode == "batched":
            return "batched"
        return "sequential"

    @property
    def effective_policy(self) -> str:
        """Interleaving needs chunked prefill dispatches to spread across
        steps; architectures on the sequential fallback serve serially."""
        if self.scfg.policy != "serial" \
                and self.effective_prefill_mode != "batched":
            return "serial"
        return self.scfg.policy

    def _chunk_bucket(self, req: Request) -> int:
        """Length bucket = prefill chunk count (what the wave's cost is
        quantized to)."""
        C = self.scfg.prefill_chunk
        return -(-max(len(req.prompt) - 1, 1) // C)

    # ---- summarization (prefill) phase ------------------------------------- #
    def admit_wave(self, limit: Optional[int] = None
                   ) -> List[Tuple[int, Request]]:
        """Admit up to ``limit`` queued requests into free slots (all free
        slots when ``limit`` is None): reset their cache rows / budgets and
        mark them resident-but-not-ready. Prefill is the caller's job —
        schedulers either run it to completion (``prefill_wave``) or spread
        it across steps via a ``PrefillJob``.

        Bucketed admission: the queue is stably sorted by chunk-count bucket
        (shortest first, arrival order within a bucket), so a wave admits
        prompts of similar length and its chunk loop is not dominated by one
        long straggler from an arbitrary FIFO mix. Aging bounds starvation:
        each wave a request is passed over lowers its effective bucket by
        one, so a long prompt outranks fresh short arrivals after at most
        `bucket` waves."""
        free = self.free_slot_ids()
        if not (free and self.queue):
            return []
        with span(self.spans, "serve.admit"):
            if self.scfg.admission == "bucketed" and len(self.queue) > 1:
                self.queue.sort(key=lambda r: max(
                    self._chunk_bucket(r) - r.deferred, 0))
            cap = len(free) if limit is None else min(limit, len(free))
            admitted: List[Tuple[int, Request]] = []
            now = time.perf_counter()   # leaves the queue for a slot
            while len(admitted) < cap and self.queue:
                admitted.append((free.pop(0), self.queue.pop(0)))
            for r in self.queue:
                r.deferred += 1
            slots = [s for s, _ in admitted]
            sl = jnp.asarray(np.array(slots))
            # one in-place reset of the admitted slots' cache rows, one
            # program whatever the wave's size
            padded = np.full(self.scfg.max_slots, self.scfg.max_slots,
                             np.int32)
            padded[:len(slots)] = slots
            self.cache = reset_slots(self.cache, jnp.asarray(padded))
            # The fused decode step writes K/V at lens[slot] for EVERY slot
            # (inactive ones included) as a dispatch side effect. While a
            # slot is mid-prefill under an interleaving policy, co-scheduled
            # decode steps must not clobber its freshly written prompt cache
            # — park its write cursor at max_len-1, a position generation
            # can never attend (termination fires before lens reaches it).
            # The sequential prefill path instead drives ``lens`` itself, so
            # it starts at 0.
            park = self.scfg.max_len - 1 \
                if self.effective_prefill_mode == "batched" else 0
            self.lens = self.lens.at[sl].set(park)
            self.gen_count = self.gen_count.at[sl].set(0)
            self.max_new = self.max_new.at[sl].set(jnp.asarray(
                [r.max_new_tokens for _, r in admitted], jnp.int32))
            for slot, req in admitted:
                self.slot_req[slot] = req
                self.slot_ready[slot] = False
                req.t_admitted = now
            # scatter checkpointed KV prefixes AFTER the batch reset:
            # restored rows land at positions [0, prefix_len) — far below
            # the parked write cursor — and the suffix prefill's masked
            # writes never touch them, so co-scheduled decode steps can't
            # clobber the restore
            restores: List[Tuple[int, int, int]] = []
            for slot, req in admitted:
                if req.restore is not None:
                    self.import_kv_snapshot(slot, req.restore, gid=req.gid,
                                            rid=req.rid)
                    restores.append((slot, req.rid, req.prefill_start))
                    req.restore = None      # payload applied; free the rows
            self.wave_count += 1
            if self.recorder is not None:
                self.recorder.on_admit(
                    self.step_idx,
                    [(int(s), r.rid, int(len(r.prompt)))
                     for s, r in admitted],
                    restores=restores)
            return admitted

    def build_prefill_job(self, wave) -> Optional[PrefillJob]:
        """Lay a wave's prompt tokens out for chunked dispatch, indexed by
        slot; ``dispatch_prefill_chunk`` takes from it only the rows of
        requests with tokens in the chunk. None when the wave has no cache
        tokens to write (all single-token prompts). With ``pack=True`` the
        wave is first-fit-decreasing packed into chunk rows
        (``plan_packed_job``) instead."""
        B, C = self.scfg.max_slots, self.scfg.prefill_chunk
        if self.scfg.pack:
            return plan_packed_job(wave, max_slots=B, chunk=C,
                                   sub_batch=self.wave_count - 1)
        S = max(len(r.prompt) - 1 for _, r in wave)
        if S == 0:
            return None
        n_chunks = -(-S // C)
        tokens = np.zeros((B, n_chunks * C), np.int32)
        valid = np.zeros((B, n_chunks * C), bool)
        for slot, req in wave:
            p = req.prompt[:-1]
            tokens[slot, :len(p)] = p
            # a restored request's prefix [0, prefill_start) is already in
            # cache: those positions stay invalid, so their rows compute as
            # masked padding and their cache writes are dropped — only the
            # uncheckpointed suffix prefills
            valid[slot, req.prefill_start:len(p)] = True
        if not valid.any():
            return None        # every wave member was fully restored
        return PrefillJob(wave=wave, tokens=tokens, valid=valid, chunk=C,
                          n_chunks=n_chunks, sub_batch=self.wave_count - 1)

    def _get_prefill_fn(self, chunk_idx: int):
        """One jitted prefill per chunk index: the offset (and therefore the
        attended KV span) is static, so chunk c compiles once and is reused
        by every later admission batch (and engine instance)."""
        return _jit_prefill(self.cfg, chunk_idx * self.scfg.prefill_chunk)

    def _account_chunk_prefill(self, job: PrefillJob, c: int,
                               vc: np.ndarray, slots: List[int], *,
                               overlap: bool, fused: bool) -> None:
        """Stats + route + trace event for one UNPACKED chunk dispatch of
        ``vc.shape[0]`` rows carrying ``slots`` (shared by the standalone
        and fused paths)."""
        R, C = vc.shape[0], job.chunk
        self.prefill_stats["token_slots"] += R * C
        self.prefill_stats["valid_tokens"] += int(vc.sum())
        self.prefill_stats["kv_cells"] += R * (c * C + C)
        entry = self._phase_entry("summarization", int(vc.sum()),
                                  len(slots))
        if self.recorder is not None:
            self.recorder.on_prefill(
                self.step_idx, offset=c * C, chunk=C,
                valid=int(vc.sum()), kv=c * C + C, slots=slots,
                route=entry, sub_batch=job.sub_batch, overlap=overlap,
                fused=fused, rows=R)

    def _account_packed_prefill(self, job: PackedPrefillJob, d, *,
                                overlap: bool, fused: bool) -> None:
        """Stats + route + trace event for one PACKED dispatch (shared by
        the standalone and fused paths)."""
        C = job.chunk
        self.prefill_stats["token_slots"] += d.token_slots
        self.prefill_stats["valid_tokens"] += d.n_valid
        self.prefill_stats["kv_cells"] += d.rows * (d.prefix_span + C)
        slots = sorted({int(s) for s in d.seg_slot[d.valid]})
        entry = self._phase_entry("summarization", d.n_valid, len(slots))
        if self.recorder is not None:
            self.recorder.on_prefill(
                self.step_idx, offset=-1, chunk=C, valid=d.n_valid,
                kv=d.prefix_span + C, slots=slots, route=entry,
                sub_batch=job.sub_batch, overlap=overlap, fused=fused,
                packed=True, segments=d.segments, rows=d.rows)

    def dispatch_prefill_chunk(self, job: PrefillJob, *,
                               overlap: bool = False) -> None:
        """Run the job's next chunk through the batched flash prefill path.
        The chunk's rows with prompt tokens go out in dispatches of
        ``prefill_rows`` rows, each row naming its slot, the last padded
        with rows whose writes drop; at one row per slot the dispatch is
        the slot grid. ``overlap=True`` marks the dispatches as
        co-scheduled with this step's decode (recorded in the trace; the
        replay merges the two streams)."""
        if isinstance(job, PackedPrefillJob):
            return self._dispatch_packed_chunk(job, overlap=overlap)
        c, C = job.next_chunk, job.chunk
        job.next_chunk += 1
        window = slice(c * C, (c + 1) * C)
        vc = job.valid[:, window]
        slots = [int(s) for s, _ in job.wave if vc[s].any()]
        if not slots:
            return
        R = self._prefill_rows
        fn = self._get_prefill_fn(c)
        for g in range(0, len(slots), R):
            group = slots[g:g + R]
            with span(self.spans, "serve.prefill"):
                if R == self.scfg.max_slots:   # the slot grid: row i, slot i
                    valid = vc
                    self.cache = fn(self.params,
                                    jnp.asarray(job.tokens[:, window]),
                                    self.cache, jnp.asarray(valid))
                else:
                    row_slot = np.full(R, group[0], np.int32)
                    row_slot[:len(group)] = group
                    valid = vc[row_slot]
                    valid[len(group):] = False
                    self.cache = fn(
                        self.params,
                        jnp.asarray(job.tokens[row_slot, window]),
                        self.cache, jnp.asarray(valid),
                        jnp.asarray(row_slot))
                self.dispatch_counts["prefill"] += 1
                self._account_chunk_prefill(job, c, valid, group,
                                            overlap=overlap, fused=False)

    def _dispatch_packed_chunk(self, job: PackedPrefillJob, *,
                               overlap: bool = False) -> None:
        """Run a PACKED dispatch: rows carry several prompts (or a long
        prompt's tail plus short prompts); per-token (slot, pos) metadata
        scatters K/V and drives the segment-aware attention mask. The grid
        shrinks to exactly the lanes the plan uses, so ``token_slots``
        counts what was computed, not max_slots rows. A
        packed event has no single offset (each row sits elsewhere in its
        prompts) so the trace records offset=-1 and the true packing."""
        d = job.dispatches[job.next_chunk]
        job.next_chunk += 1
        with span(self.spans, "serve.prefill"):
            fn = _jit_prefill_packed(self.cfg, d.prefix_span)
            self.cache = fn(self.params, jnp.asarray(d.tokens), self.cache,
                            jnp.asarray(d.seg_slot), jnp.asarray(d.seg_pos),
                            jnp.asarray(d.seg_ids), jnp.asarray(d.valid),
                            jnp.asarray(d.row_slot),
                            jnp.asarray(d.prefix_len))
            self.dispatch_counts["prefill"] += 1
            self._account_packed_prefill(job, d, overlap=overlap,
                                         fused=False)

    def finish_prefill(self, wave) -> None:
        """A wave's prompt is fully cached: arm the slots for generation
        (prompt[:-1] filled the cache; the last prompt token is the first
        generation step's input)."""
        with span(self.spans, "serve.arm"):
            sl = jnp.asarray(np.array([s for s, _ in wave]))
            plens = np.array([len(r.prompt) for _, r in wave])
            self.lens = self.lens.at[sl].set(
                jnp.asarray(plens - 1, jnp.int32))
            last = np.array([r.prompt[-1] for _, r in wave], np.int32)
            self.last_tok = self.last_tok.at[sl].set(jnp.asarray(last))
            for slot, _ in wave:
                self.slot_ready[slot] = True

    def prefill_wave(self, wave) -> None:
        """Serial-policy prefill: run the whole wave to completion within
        the admission step (batched chunk loop or sequential fallback)."""
        if self.effective_prefill_mode == "batched":
            job = self.build_prefill_job(wave)
            if job is not None:
                while not job.done:
                    self.dispatch_prefill_chunk(job)
        else:
            self._prefill_sequential(wave)
        self.finish_prefill(wave)

    def _admit(self) -> None:
        """Legacy serial admission (kept for callers that drive prefill
        directly, e.g. benchmarks/serve_prefill.py): admit every free slot
        and prefill to completion."""
        wave = self.admit_wave()
        if wave:
            self.prefill_wave(wave)

    def _prefill_sequential(self, wave) -> None:
        """Reference path (and fallback for SSM/hybrid/encdec stacks):
        teacher-forced decode steps, one dispatch + host sync per token."""
        for slot, req in wave:
            for pos, tok in enumerate(req.prompt[:-1]):
                with span(self.spans, "serve.prefill"):
                    t = jnp.zeros((self.scfg.max_slots, 1), jnp.int32
                                  ).at[slot, 0].set(int(tok))
                    _logits, self.cache = self._decode(
                        self.params, t, self.cache, self.lens)
                    self.lens = self.lens.at[slot].add(1)
                self.dispatch_counts["prefill"] += 1
                # each teacher-forced dispatch computes a (B, 1) grid with
                # exactly one useful row — count it, or valid-token-fraction
                # reports are silently wrong for SSM/hybrid fallback waves
                self.prefill_stats["token_slots"] += self.scfg.max_slots
                self.prefill_stats["valid_tokens"] += 1
                self.prefill_stats["kv_cells"] += \
                    self.scfg.max_slots * (pos + 1)
            n_valid = max(len(req.prompt) - 1, 0)
            entry = self._phase_entry("summarization", n_valid, len(wave))
            if self.recorder is not None and n_valid:
                self.recorder.on_prefill(
                    self.step_idx, offset=0, chunk=n_valid, valid=n_valid,
                    kv=n_valid, slots=[slot], route=entry,
                    sub_batch=self.wave_count - 1, overlap=False,
                    rows=self.scfg.max_slots)

    # ---- generation phase: one fused decode dispatch across ready slots ---- #
    def _ready_active(self) -> Tuple[Optional[np.ndarray], int]:
        """(active mask, count) over decode-ready slots; (None, 0) when no
        slot is ready — the shared prologue of every decode dispatch."""
        ready = self.ready_slot_ids()
        if not ready:
            return None, 0
        active_np = np.zeros((self.scfg.max_slots,), bool)
        active_np[ready] = True
        return active_np, len(ready)

    def _phase_entry(self, phase: str, n_tokens: int, active: int) -> dict:
        """Route record for one dispatch; a PIM-degraded engine forces the
        NPU/MU path (``force_mu``) so its trace replays NPU-only."""
        return phase_log_entry(phase, n_tokens, active,
                               self.cfg.d_model, self.cfg.d_ff,
                               force_mu=self.degraded)

    def _start_fetch(self, fetch) -> None:
        """Double-buffered fetch: start the result's device->host copy at
        dispatch so co-scheduled work overlaps the transfer."""
        if self.scfg.double_buffer and hasattr(fetch, "copy_to_host_async"):
            fetch.copy_to_host_async()
            self.async_fetches += 1

    def dispatch_decode(self, *, overlap: bool = False
                        ) -> Optional[PendingDecode]:
        """Issue the fused decode+sample+terminate dispatch for every ready
        slot and start the result's async device->host copy (double-buffered
        fetch): the blocking sync happens in ``resolve_decode``, after the
        scheduler has issued whatever it co-schedules in between."""
        active_np, n_tok = self._ready_active()
        if active_np is None:
            return None
        with span(self.spans, "serve.decode"):
            entry = self._phase_entry("generation", n_tok, n_tok)
            (fetch, self.cache, self.last_tok, self.lens, self.gen_count,
             self._rng) = self._decode_sample(
                self.params, self.cache, self.last_tok, self.lens,
                jnp.asarray(active_np), self.gen_count, self.max_new,
                self._rng)
            self.dispatch_counts["decode"] += 1
            self._start_fetch(fetch)
            return PendingDecode(fetch=fetch, active_np=active_np, n_tok=n_tok,
                                 route=entry, overlap=overlap)

    def dispatch_fused_step(self, job) -> PendingDecode:
        """Issue ONE dispatch carrying the resident batch's decode AND the
        job's next prefill chunk (``T.fused_step[_packed]``) — the
        single-program realization of an overlapped step. The caller
        guarantees a non-empty decode batch and a chunk with valid tokens;
        counted as one ``fused`` dispatch (neither a prefill nor a decode
        one), traced as a fused prefill + decode event pair."""
        active_np, n_tok = self._ready_active()
        assert active_np is not None, \
            "fused step needs a resident decode batch"
        with span(self.spans, "serve.decode"):
            dentry = self._phase_entry("generation", n_tok, n_tok)
            C = self.scfg.prefill_chunk
            common = (self.last_tok, self.lens, jnp.asarray(active_np),
                      self.gen_count, self.max_new, self._rng)
            if isinstance(job, PackedPrefillJob):
                d = job.dispatches[job.next_chunk]
                job.next_chunk += 1
                fn = _jit_fused_step_packed(
                    self.cfg, self.scfg.temperature, self.scfg.eos_token,
                    self.scfg.max_len, d.prefix_span)
                (fetch, self.cache, self.last_tok, self.lens, self.gen_count,
                 self._rng) = fn(
                    self.params, self.cache, jnp.asarray(d.tokens),
                    jnp.asarray(d.seg_slot), jnp.asarray(d.seg_pos),
                    jnp.asarray(d.seg_ids), jnp.asarray(d.valid),
                    jnp.asarray(d.row_slot), jnp.asarray(d.prefix_len),
                    *common)
                self._account_packed_prefill(job, d, overlap=True, fused=True)
            else:
                c = job.next_chunk
                job.next_chunk += 1
                vc = job.valid[:, c * C:(c + 1) * C]
                assert vc.any(), "fused step dispatched an empty prefill chunk"
                fn = _jit_fused_step(
                    self.cfg, self.scfg.temperature, self.scfg.eos_token,
                    self.scfg.max_len, c * C)
                (fetch, self.cache, self.last_tok, self.lens, self.gen_count,
                 self._rng) = fn(
                    self.params, self.cache,
                    jnp.asarray(job.tokens[:, c * C:(c + 1) * C]),
                    jnp.asarray(vc), *common)
                self._account_chunk_prefill(
                    job, c, vc, [int(s) for s, _ in job.wave if vc[s].any()],
                    overlap=True, fused=True)
            self.dispatch_counts["fused"] += 1
            self._start_fetch(fetch)
            return PendingDecode(fetch=fetch, active_np=active_np, n_tok=n_tok,
                                 route=dentry, overlap=True, fused=True)

    def dispatch_decode_superstep(self, k: int
                                  ) -> Optional[PendingSuperstep]:
        """Issue ONE dispatch running up to k decode steps (``lax.scan``
        with on-device sampling and termination; finished lanes freeze).
        Resolves one (k, 3, B) fetch instead of k (3, B) fetches — counted
        as a single decode dispatch. The routing entry is decided ONCE at
        dispatch (the scanned program cannot re-route mid-flight), so all k
        inner trace events share it by design even when lanes terminate
        mid-span — the divergence report then measures exactly that
        per-dispatch commitment against Algorithm 1's per-round mapping."""
        active_np, n_tok = self._ready_active()
        if active_np is None:
            return None
        with span(self.spans, "serve.decode"):
            entry = self._phase_entry("generation", n_tok, n_tok)
            fn = _jit_decode_superstep(self.cfg, self.scfg.temperature,
                                       self.scfg.eos_token,
                                       self.scfg.max_len, k)
            (fetch, self.cache, self.last_tok, self.lens, self.gen_count,
             self._rng) = fn(
                self.params, self.cache, self.last_tok, self.lens,
                jnp.asarray(active_np), self.gen_count, self.max_new,
                self._rng)
            self.dispatch_counts["decode"] += 1
            self._start_fetch(fetch)
            sid = self._superstep_seq
            self._superstep_seq += 1
            return PendingSuperstep(fetch=fetch, active_np=active_np, k=k,
                                    route=entry, sid=sid)

    @staticmethod
    def _append_token(req: Request, tok: int, now: float) -> None:
        if not req.generated:
            req.t_first_token = now
        req.generated.append(tok)

    def _finish_slot(self, i: int) -> None:
        """Retire a slot whose request just terminated: free it, record the
        completion (shared by single-step and superstep resolve)."""
        r = self.slot_req[i]
        r.done = True
        r.t_done = time.perf_counter()
        self.slot_req[i] = None
        self.slot_ready[i] = False
        if self.recorder is not None:
            if self.scfg.eos_token is not None \
                    and r.generated[-1] == self.scfg.eos_token:
                reason = "eos"
            elif len(r.generated) >= r.max_new_tokens:
                reason = "max_new"
            else:
                reason = "cache_full"
            self.recorder.on_complete(self.step_idx, r.rid, reason,
                                      len(r.generated))

    def resolve_decode(self, pending: PendingDecode
                       ) -> List[Tuple[int, int]]:
        """Materialize a dispatched decode step's (token, done, len) triple
        — the step's single blocking host sync — and apply its results:
        token append, trace events, completions."""
        with span(self.spans, "serve.fetch"):
            fetch_np = np.asarray(pending.fetch)
        self.host_syncs += 1
        with span(self.spans, "serve.apply"):
            now = time.perf_counter()
            toks_np, done_np, lens_np = (
                fetch_np[0], fetch_np[1].astype(bool), fetch_np[2])
            active_idx = np.nonzero(pending.active_np)[0]
            out = [(self.slot_req[i].rid, int(toks_np[i]))
                   for i in active_idx]
            for i, (rid, tok) in zip(active_idx, out):
                self._append_token(self.slot_req[i], tok, now)
            if self.recorder is not None:
                # decode event first: completions reference its token
                self.recorder.on_decode(
                    self.step_idx, occupancy=pending.n_tok,
                    slot_lens=[int(x) for x in lens_np],
                    slots=[int(i) for i in active_idx],
                    tokens=list(out), route=pending.route,
                    overlap=pending.overlap, fused=pending.fused)
            for i in active_idx:
                if done_np[i]:
                    self._finish_slot(i)
            return out

    def resolve_decode_superstep(self, pending: PendingSuperstep
                                 ) -> List[Tuple[int, int]]:
        """Materialize a superstep's (k, 3, B) fetch — ONE blocking host
        sync for k generation steps — and expand it into the per-step
        results: tokens append in inner-step order, each inner step records
        its own decode event (schema v4 ``superstep`` span), completions
        fire at the inner step where the lane terminated, and the engine
        clock advances one step per inner step so open-loop arrival timing
        stays one-decode-round-per-tick."""
        with span(self.spans, "serve.fetch"):
            fetch_np = np.asarray(pending.fetch)      # (k, 3, B)
        self.host_syncs += 1
        with span(self.spans, "serve.apply"):
            now = time.perf_counter()
            out: List[Tuple[int, int]] = []
            active = pending.active_np.copy()
            for i in range(pending.k):
                if i:
                    self.step_idx += 1   # inner steps advance the timeline
                idx = np.nonzero(active)[0]
                if idx.size == 0:
                    continue             # lanes drained early; clock ran
                toks_np = fetch_np[i, 0]
                done_np = fetch_np[i, 1].astype(bool)
                lens_np = fetch_np[i, 2]
                step_out = [(self.slot_req[s].rid, int(toks_np[s]))
                            for s in idx]
                for s, (_rid, tok) in zip(idx, step_out):
                    self._append_token(self.slot_req[s], tok, now)
                self.superstep_tokens += 1
                if self.recorder is not None:
                    self.recorder.on_decode(
                        self.step_idx, occupancy=int(idx.size),
                        slot_lens=[int(x) for x in lens_np],
                        slots=[int(s) for s in idx],
                        tokens=list(step_out), route=pending.route,
                        overlap=False, superstep=pending.k,
                        superstep_id=pending.sid)
                for s in idx:
                    if done_np[s]:
                        self._finish_slot(s)
                active &= ~done_np
                out.extend(step_out)
            return out

    # ---- step: composition delegated to the scheduling policy --------------- #
    def step(self) -> List[Tuple[int, int]]:
        if self.halted:
            raise RuntimeError("engine is halted (crashed node); a crashed "
                               "replica must never dispatch again")
        step = self.step_idx
        with span(self.spans, "serve.step", step=step):
            out = self.scheduler.step(self)
        self.step_idx += 1     # idle steps still advance the timeline
        self.spans.record(step, self.step_idx - step, self.scheduler.kind)
        return out             # (open-loop arrival processes need a clock)

    def run_until_done(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        results: Dict[int, List[int]] = {}
        for _ in range(max_steps):
            if not self.queue and all(r is None for r in self.slot_req):
                break
            for rid, tok in self.step():
                results.setdefault(rid, []).append(tok)
        return results
