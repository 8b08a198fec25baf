"""Batched serving engine: prefill -> decode with KV-cache handoff.

Continuous-batching-lite with a **phase-gated** slot refill: the decode
batch is a phaser team (DESIGN.md §3) — every decode step is one phase,
each occupied slot is a participant, and batch-membership changes ride
the same epoch mechanism as elastic training:

* a request entering a free slot is a JOIN (the paper's eager insertion:
  prefill + cache splice happen immediately, at the step boundary, and
  no running request is disturbed);
* a finished request is a LEAVE (deletion: the phase completes without
  it and the slot is reclaimed);
* the runtime's epoch index versions the batch composition — the swap is
  observable only at phase boundaries, so a step never sees a
  half-admitted batch.

Admission is **bulk**: all free slots are filled at the same phase
boundary, grouped by prompt length **padded up to a power-of-two
bucket**, and the admission *group size* is padded up to a power-of-two
row bucket too (clamped to the slot count) — so admission compiles ONE
prefill executable per (length bucket, group bucket) instead of one per
distinct (prompt length, group size): a boundary that happens to admit
3 requests hits the executable the 4-request boundary compiled. Each
group runs one full-logits prefill over the padded prompts (a single
forward instead of one decode step per token); causality keeps every
position below a request's true length unaffected by the pad tail, so
the engine reads each request's next token at its own ``len - 1`` and
splices only the first ``len`` KV positions into the slot's cache
region, without touching running slots; the pad ROWS' outputs are
simply sliced away before the splice.

Families whose decode state is a **recurrence** (ssm / xlstm / hybrid)
cannot splice a full-logits prefill's caches — their state is the
O(1) carry after the prompt, not a per-position buffer. They get their
own bulk path (``ModelAPI.prefill_state_fn``): one compiled
length-masked decode scan over the padded group (a slot's state freezes
at its true length), spliced into the admitted slots in one vectorized
scatter. That replaces G x len full-batch decode dispatches per group
with ONE jitted call per (group size, bucket) — the recurrent analogue
of the KV cache splice. Enc-dec/vlm and prompts longer than the cache
window keep the token-by-token path.

Correctness note (the bug this design fixed): anything handed to the
async-dispatched jitted decode must be an immutable snapshot. Passing a
live numpy buffer zero-copy and then mutating it in place (the next
prefill token, ``slot_pos[i] += 1``) races the pending execution —
flakily, since the window depends on dispatch latency. All device inputs
therefore go through ``utils.to_device_copy``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models.registry import ModelAPI
from ..obs.metrics import MetricsRegistry
from ..obs.timeline import span
from ..runtime_elastic.elastic_phaser import ElasticPhaserRuntime
from ..utils import to_device_copy


@dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (S,) int32
    max_new: int = 16
    out: List[int] = field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0           # stamped by submit(); queue-wait base


class ServeEngine:
    def __init__(self, api: ModelAPI, params, *, batch: int = 4,
                 window: int = 256, seed: int = 0):
        self.api = api
        self.cfg = api.cfg
        self.params = params
        self.batch = batch
        self.window = window
        self.state = api.init_decode_state(batch, window)
        self.slot_req: List[Optional[Request]] = [None] * batch
        self.slot_pos = np.zeros((batch,), np.int32)
        self.queue: List[Request] = []
        # per-engine metrics shard (obs plane), shared with the gate so
        # one shard holds the whole serve path: trace counters,
        # admission kinds, queue wait, and the spans ``serve.admit``,
        # ``serve.decode`` and the gate's ``phaser.*``. The legacy
        # ``prefill_traces``/``prefill_state_traces`` attributes are
        # read-only views over these counters.
        self.metrics = MetricsRegistry()
        # control plane: occupied slots are phaser participants; admission
        # keys are monotone (a slot reused by a later request is a new
        # participant — phaser keys are never recycled)
        self.gate = ElasticPhaserRuntime(0, seed=seed, axis_name="slots",
                                         metrics=self.metrics)
        self.slot_key: List[Optional[int]] = [None] * batch
        self.finished: List[Request] = []
        # no donation: _admit snapshots the pre-prefill state for splicing
        self._decode = jax.jit(api.decode_fn)
        # full-logits prefill: length-bucketed groups read each
        # request's next token at its true len-1, not the padded tail.
        # The trace counters tick ONCE per lowering (the wrapped python
        # body only runs at trace time): regression tests assert a new
        # admission group size re-uses the cached executable.

        def _pf(p, b):
            self.metrics.inc("serve.prefill.traces")
            return api.prefill_full_fn(p, b)

        self._prefill = jax.jit(_pf)
        # per-leaf batch dim: the dim whose size changes with the batch
        # (needed to splice a newly-prefilled slot into the live state
        # without touching other slots)
        self._bdim = api.decode_state_bdims(batch, window)
        # bulk-prefill eligibility: decode state must be the plain stacked
        # KV cache whose layout prefill_fn's caches splice into directly
        layers = self.state.get("layers")
        self._bulk = (self.cfg.family in ("dense", "moe")
                      and not self.cfg.is_encdec
                      and isinstance(layers, dict)
                      and set(layers) == {"k", "v", "pos"})
        self._kv_window = layers["k"].shape[2] if self._bulk else 0
        # recurrent families take the length-masked decode-scan bulk
        # path instead (xlstm is family "ssm" with slstm groups)
        self._bulk_rec = (self.cfg.family in ("ssm", "hybrid")
                          and not self.cfg.is_encdec)
        # one compiled scan per (group bucket, length bucket) — the
        # window is static and the group dim pads to pow2 rows
        def _ps(p, toks, lens):
            self.metrics.inc("serve.prefill_state.traces")
            return api.prefill_state_fn(p, toks, lens, window=window)

        self._prefill_state = jax.jit(_ps)

    @property
    def prefill_traces(self) -> int:
        """Compat view: lowerings of the full-logits prefill."""
        return self.metrics.counter("serve.prefill.traces").value

    @property
    def prefill_state_traces(self) -> int:
        """Compat view: lowerings of the recurrent prefill scan."""
        return self.metrics.counter("serve.prefill_state.traces").value

    @property
    def epoch(self) -> int:
        """Batch-membership epoch (bumps at the boundary after any
        admit/retire, exactly like the training runtime)."""
        return self.gate.epoch.index

    def _splice_slot(self, old_state, new_state, slot: int):
        """Keep ``new_state`` only at ``slot``; other slots keep ``old``
        (admitting a request must not disturb running ones — recurrent
        states would otherwise be corrupted by the admit steps)."""
        def f(o, n, d):
            idx = jnp.arange(o.shape[d])
            shape = [1] * o.ndim
            shape[d] = -1
            return jnp.where((idx == slot).reshape(shape), n, o)
        return jax.tree_util.tree_map(f, old_state, new_state, self._bdim)

    def _dispatch(self, token_b: np.ndarray, pos_b: np.ndarray):
        """One jitted decode call. Inputs are SNAPSHOTTED into fresh
        buffers owned by this call (``to_device_copy``): the
        host-to-device transfer may alias the source buffer and read it
        asynchronously, so handing it a buffer the caller mutates right
        after dispatch (the next prefill token, ``slot_pos[i] += 1``)
        races the pending execution (see module docstring)."""
        return self._decode(
            self.params, self.state,
            {"token": to_device_copy(token_b, dtype=np.int32),
             "t": to_device_copy(pos_b, dtype=np.int32)})

    # ------------------------------------------------------------- intake
    def submit(self, req: Request) -> None:
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    @staticmethod
    def _bucket_len(length: int) -> int:
        """Prompt lengths pad up to power-of-two buckets, so admission
        compiles one prefill per (group bucket, length bucket) instead
        of one per distinct prompt length."""
        return 1 << max(0, (length - 1)).bit_length()

    def _bucket_group(self, n: int) -> int:
        """Admission group sizes pad up to power-of-two ROW buckets
        (clamped to the slot count — a group can never exceed the
        batch), the same trick as prompt-length buckets: one compiled
        prefill/decode-scan executable per (length bucket, group
        bucket) serves every admission size."""
        return min(self._bucket_len(max(1, n)), self.batch)

    def _admit(self) -> None:
        """Phase-boundary refill: fill ALL free slots from the queue at
        this boundary (JOIN = eager insertion). Admits are batched: bulk
        groups (same power-of-two length bucket) run one padded prefill
        forward (KV families) or one length-masked decode scan
        (recurrent families) each and splice their states in; everything
        else falls back to token-by-token prefill. The ``serve.admit``
        span covers the prefills, splices and first-token read-backs."""
        admits: List[Tuple[int, Request]] = []
        for slot in range(self.batch):
            if self.slot_req[slot] is None and self.queue:
                admits.append((slot, self.queue.pop(0)))
        if admits:
            with span("serve.admit", self.metrics,
                      rids=[r.rid for _, r in admits]):
                self._admit_groups(admits)

    def _admit_groups(self, admits: List[Tuple[int, Request]]) -> None:
        groups: Dict[Tuple[str, int], List[Tuple[int, Request]]] = {}
        for slot, req in admits:
            # clamp to the window so a non-pow2 window keeps its largest
            # admissible prompts on the bulk path (they share one
            # window-sized bucket)
            L = len(req.prompt)
            if self._bulk and L <= self._kv_window:
                bucket = min(self._bucket_len(L), self._kv_window)
                groups.setdefault(("kv", bucket), []).append((slot, req))
            elif self._bulk_rec and L <= self.window:
                bucket = min(self._bucket_len(L), self.window)
                groups.setdefault(("rec", bucket), []).append((slot, req))
            else:
                self.metrics.inc("serve.admit.sequential")
                self._admit_sequential(slot, req)
        for (kind, bucket), group in sorted(groups.items()):
            self.metrics.inc(f"serve.admit.{kind}", len(group))
            if kind == "kv":
                self._admit_bulk(group, bucket)
            else:
                self._admit_bulk_recurrent(group, bucket)

    def _admit_bulk(self, group: List[Tuple[int, "Request"]],
                    bucket: int) -> None:
        """One padded prefill forward over the whole group (rows padded
        to the pow2 group bucket), then splice each slot's cache region
        up to its TRUE prompt length (running slots untouched; neither
        the pad tail's KV nor the pad rows ever enter the cache)."""
        G = len(group)
        lengths = [len(r.prompt) for _, r in group]
        tokens = np.zeros((self._bucket_group(G), bucket), np.int32)
        for g, (_, r) in enumerate(group):
            tokens[g, :lengths[g]] = r.prompt
        logits, caches = self._prefill(self.params,
                                       {"tokens": to_device_copy(tokens)})
        # drop the pad rows: only the true group reaches the splice
        logits = logits[:G]
        caches = {**caches,
                  "layers": {k: v[:, :G]
                             for k, v in caches["layers"].items()}}
        self.state = self._splice_prefill(self.state, caches,
                                          [s for s, _ in group], lengths)
        # next token at each request's own last REAL position
        nxt = np.asarray(jnp.argmax(
            logits[jnp.arange(len(group)),
                   jnp.asarray(lengths) - 1], axis=-1))
        for g, (slot, req) in enumerate(group):
            self._occupy(slot, req, int(nxt[g]), lengths[g])

    def _splice_prefill(self, state, caches, slots: List[int],
                        lengths: List[int]):
        """Write the prefilled per-layer KV into the admitted slots'
        cache regions. One vectorized set per tensor over the whole
        group (not one per slot — each eager ``.at[].set`` copies the
        full cache): k/v take the entire padded bucket, and the pos
        mask validates only 0..len_i-1 per slot, so the pad tail's KV
        stays masked out of attention (kpos -1 = padding) exactly as if
        it were never written. Every other slot's cache is untouched."""
        st = state["layers"]
        pf = caches["layers"]
        bucket = pf["k"].shape[2]
        sl = jnp.asarray(slots)
        pos = jnp.arange(bucket, dtype=jnp.int32)
        valid = pos[None] < jnp.asarray(lengths, jnp.int32)[:, None]
        new = dict(st)
        new["k"] = st["k"].at[:, sl, :bucket].set(
            pf["k"].astype(st["k"].dtype))
        new["v"] = st["v"].at[:, sl, :bucket].set(
            pf["v"].astype(st["v"].dtype))
        # invalidate the slot's WHOLE window first: a reused slot whose
        # previous prompt was longer than this bucket would otherwise
        # keep stale attendable pos rows beyond the new region
        new["pos"] = st["pos"].at[:, sl].set(-1).at[:, sl, :bucket].set(
            jnp.broadcast_to(jnp.where(valid, pos[None], -1),
                             (st["pos"].shape[0], len(slots), bucket)))
        return {**state, "layers": new}

    def _admit_bulk_recurrent(self, group: List[Tuple[int, "Request"]],
                              bucket: int) -> None:
        """Bulk admission for recurrent-state families: ONE compiled
        length-masked decode scan over the padded group
        (``prefill_state_fn``) produces every request's final recurrent
        state and its next-token logits at its own ``len - 1``; the
        states splice into the admitted slots in one vectorized scatter
        (running slots untouched). The group dim pads to the pow2 group
        bucket (pad rows scan length-1 dummies and are sliced away), so
        a new admission size hits the cached executable."""
        G = len(group)
        Gp = self._bucket_group(G)
        lengths = [len(r.prompt) for _, r in group]
        tokens = np.zeros((Gp, bucket), np.int32)
        for g, (_, r) in enumerate(group):
            tokens[g, :lengths[g]] = r.prompt
        pad_lens = np.ones((Gp,), np.int32)
        pad_lens[:G] = lengths
        logits, gstate = self._prefill_state(
            self.params, to_device_copy(tokens),
            to_device_copy(pad_lens, dtype=np.int32))
        gstate = jax.tree_util.tree_map(
            lambda leaf, d: jnp.moveaxis(
                jnp.moveaxis(leaf, d, 0)[:G], 0, d),
            gstate, self._bdim)
        self.state = self._splice_state_group(self.state, gstate,
                                              [s for s, _ in group])
        nxt = np.asarray(jnp.argmax(logits[:G], axis=-1))
        for g, (slot, req) in enumerate(group):
            self._occupy(slot, req, int(nxt[g]), lengths[g])

    def _splice_state_group(self, state, gstate, slots: List[int]):
        """Scatter a group-batched decode state (leading batch = the
        group) into the live state's admitted slots, one vectorized set
        per leaf along its batch dim."""
        sl = jnp.asarray(slots)

        def f(o, n, d):
            om = jnp.moveaxis(o, d, 0)
            nm = jnp.moveaxis(n, d, 0)
            return jnp.moveaxis(om.at[sl].set(nm.astype(om.dtype)), 0, d)

        return jax.tree_util.tree_map(f, state, gstate, self._bdim)

    def _admit_sequential(self, slot: int, req: "Request") -> None:
        """Fallback admission for recurrent-state families and prompts
        beyond the cache window: prefill via decode steps, then splice
        only this slot's state back."""
        old_state = self.state
        # a REUSED slot still holds the previous request's state: a
        # recurrent carry (or stale KV pos rows) would leak into this
        # prefill — reset the slot to a fresh init first
        self.state = self._splice_slot(
            old_state, self.api.init_decode_state(self.batch, self.window),
            slot)
        token_b = np.zeros((self.batch,), np.int32)
        logits = None
        for t, tok in enumerate(req.prompt):
            token_b[slot] = tok
            logits, self.state = self._dispatch(
                token_b, self._pos_with(slot, t))
        self.state = self._splice_slot(old_state, self.state, slot)
        self._occupy(slot, req, int(jnp.argmax(logits[slot])),
                     len(req.prompt))

    def _occupy(self, slot: int, req: "Request", first_tok: int,
                length: int) -> None:
        # admission completes here: submit -> first token in a slot is
        # the request's queue wait (histogram buckets give p50/p99)
        if req.t_submit:
            self.metrics.observe("serve.admit.queue_wait_seconds",
                                 time.perf_counter() - req.t_submit)
        req.out.append(first_tok)
        self.slot_key[slot] = self.gate.request_join()
        self.slot_req[slot] = req
        self.slot_pos[slot] = length
        if len(req.out) >= req.max_new:
            req.done = True
            self._retire(slot)

    def _retire(self, slot: int) -> None:
        """LEAVE: the finished request's participant deregisters; the
        slot is reclaimed for the next boundary's refill."""
        self.finished.append(self.slot_req[slot])
        self.gate.request_leave(self.slot_key[slot])
        self.slot_key[slot] = None
        self.slot_req[slot] = None

    def _pos_with(self, slot: int, t: int) -> np.ndarray:
        pos = self.slot_pos.copy()
        pos[slot] = t
        return pos

    # -------------------------------------------------------------- serve
    def step(self) -> int:
        """One decode step == one phase over the live batch; returns the
        number of active slots. Membership changes (admits at the leading
        boundary, retires at the trailing one) land as gate epochs."""
        self._admit()
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            if self.gate.pending_churn:
                # a request was admitted AND retired inside _admit (e.g.
                # max_new reached at prefill): its join/leave must still
                # land as an epoch at this boundary
                self.gate.advance()
            return 0
        token_b = np.zeros((self.batch,), np.int32)
        for i in active:
            r = self.slot_req[i]
            token_b[i] = r.out[-1] if r.out else r.prompt[-1]
        # np.asarray forces the device sync: the span is the real
        # per-token decode latency of the whole batch, tokens on the host
        with span("serve.decode", self.metrics):
            logits, self.state = self._dispatch(token_b, self.slot_pos)
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
        for i in active:
            r = self.slot_req[i]
            r.out.append(int(nxt[i]))
            self.slot_pos[i] += 1
            if len(r.out) >= r.max_new:
                r.done = True
                self._retire(i)     # slot freed -> next boundary refills
        # the step's phase: every live participant signals, the advance
        # marks the boundary where this step's churn becomes the new epoch
        self.gate.advance()
        return len(active)

    def run_until_drained(self, max_steps: int = 10_000) -> List[Request]:
        """Drive steps until queue and batch are empty; returns the
        requests finished during the drain, in completion order."""
        mark = len(self.finished)
        for _ in range(max_steps):
            n = self.step()
            if n == 0 and not self.queue:
                break
        return self.finished[mark:]
