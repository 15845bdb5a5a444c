"""Disaggregated edge–cloud SQS speculative decoding engine.

The paper's Algorithm 1 is realised as TWO actors with a typed wire
boundary (``core.wire``) between them — the shape a real edge-cloud
deployment has, rather than one object that drafts and verifies in
lock-step:

  ``EdgeDraftEngine``   — the device side: SLM decode scan, SQS
      sparsify/quantize (``core.sqs`` + ``core.slq``), conformal β
      state, bit-budget truncation L^t, payload packing, optimistic
      continuation (speculative drafting of round t+1 while round t is
      in flight), and verdict application (emit, β resume, rollback).

  ``CloudVerifyEngine`` — the datacenter side: payload unpacking, LLM
      parallel verify (``core.verify``), paged-KV rollback, conformal β
      backtrack (Alg. 1 lines 12–13, computed from the wire β
      trajectory), verdict packing.

They communicate ONLY through ``wire.DraftPayload`` / ``VerdictPayload``
bytes: every round the draft distributions cross the boundary as packed
lattice counts and are reconstructed bit-exactly on the cloud, so the
Quantize-and-Sample acceptance guarantee holds against the *transmitted*
q̂, and ``len(bytes) * 8`` — not a formula — is what the serving layer
charges to the shared uplink.

``EdgeCloudEngine`` remains the public facade: same constructor, the
same ``prefill / run_round / run`` batch API and the same
``init_slots / admit_slot / release_slot`` session-slot API as before
the split — it owns the slot lifecycle and the (mirrored) page
allocator and moves payloads between the two actors in lockstep.  The
event-driven serving loop (``repro.serve.events``) instead drives the
per-slot methods (``draft_slots`` / ``verify_slots`` /
``apply_verdict_slot`` / speculative drafting) so draft, uplink, verify
and downlink of different requests overlap in time.

Replay discipline (what makes out-of-lockstep calls safe): every jitted
step runs the full static batch, so rows outside the call's commit mask
still flow through the compute.  Each actor keeps *replay registers* —
the exact inputs (token, position, β, PRNG key) of every row's last
committed step.  Non-committed rows are fed their registers, so they
bit-identically re-execute their previous step: the recompute rewrites
the same cache values it wrote before, and nothing the row later reads
is perturbed.  This is why a request's token stream is independent of
which other requests share the batch AND of how calls interleave in
time — the property the lockstep-vs-pipelined and solo-vs-batched
equivalence tests assert.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import bits as bits_mod
from repro.core import channel as channel_mod
from repro.core import conformal
from repro.core import sqs as sqs_mod
from repro.core import verify as verify_mod
from repro.core import wire as wire_mod
from repro.core.pages import PageAllocator
from repro.models import model as model_mod
from repro.models.attention import PagedSpec, sanitize_page_table
from repro.obs.handle import NULL_OBS, Obs

SEQ_BLOCKS = ("mamba", "mlstm", "slstm")


@dataclasses.dataclass(frozen=True)
class MethodConfig:
    name: str = "csqs"               # ksqs | csqs | qs | uncompressed
    K: int = 64                      # K-SQS cardinality
    ell: int = 100                   # lattice resolution ℓ
    alpha: float = 5e-4              # C-SQS target deviation
    eta: float = 1e-3                # C-SQS learning rate
    beta0: float = 1e-3              # C-SQS initial threshold
    use_kernels: bool = False        # Pallas fused SQS path (repro.kernels)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    L_max: int = 8                   # max drafts per batch
    bit_budget: float = 5000.0       # uplink budget B per batch (bits)
    temperature: float = 1.0
    collect_theory: bool = False     # keep dense q/p for Theorem-1 logging
    # Wire codec version negotiated for the link (core.wire.CODECS):
    # "v1" fixed-width fields, "v2" entropy-coded (core.coding).  A
    # request may override it at admission (admit_slot(wire_codec=...)).
    wire_codec: str = "v1"
    # How the edge estimates per-token wire bits when truncating L^t:
    # "analytic"   — the paper's eq. (1) budget, codec-independent (so
    #                token streams are identical across codec versions);
    # "calibrated" — analytic × a per-request online scale (EMA of
    #                observed coded size / analytic estimate), so the
    #                budget tracks what the active codec REALLY ships.
    budget_model: str = "analytic"


def _is_stateful(cfg: ModelConfig) -> bool:
    return any(b in SEQ_BLOCKS for b in cfg.block_pattern)


def _seq_periods(cfg: ModelConfig):
    return [f"p{i}" for i in range(cfg.period)
            if cfg.block_pattern[i] in SEQ_BLOCKS]


def row_key(seed: int, row: int = 0):
    """Per-row PRNG root: fold the row index into the stream seed.  A
    request admitted with ``seed`` into ANY slot gets row_key(seed, 0) —
    identical to row 0 of a solo EdgeCloudEngine(seed=seed) run."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), row)


def cloud_row_key(seed: int, row: int = 0):
    """The cloud actor's independent per-row PRNG root (verification
    randomness lives in the datacenter, never on the wire)."""
    return jax.random.fold_in(row_key(seed, row), 0x0C10)


def _split_rows(keys, num: int = 2):
    """keys: (B, 2) -> (num, B, 2) independent per-row subkeys."""
    kk = jax.vmap(lambda k: jax.random.split(k, num))(keys)
    return tuple(kk[:, i] for i in range(num))


def rollback_cache(cfg: ModelConfig, cache, traj, n_keep):
    """Restore sequential-state leaves to the snapshot after position
    ``n_keep − 1`` (n_keep ≥ 1 tokens kept).  Positional (KV) leaves need
    no rollback.  traj leaves: (N, B, S, ...); cache leaves: (N, B, ...)."""
    if traj is None:
        return cache
    idx = jnp.maximum(n_keep - 1, 0)

    def select(t):
        ix = idx.reshape((1, -1, 1) + (1,) * (t.ndim - 3))
        return jnp.take_along_axis(t, ix, axis=2)[:, :, 0]

    new_body = dict(cache["body"])
    for pname in _seq_periods(cfg):
        new_body[pname] = jax.tree.map(select, traj[pname])
    out = dict(cache)
    out["body"] = new_body
    return out


# ======================================================================
# Host-side round records (what crosses between serving-loop events)
# ======================================================================
@dataclasses.dataclass
class PendingRound:
    """Edge-side record of one in-flight SD round for one slot: enough
    to apply the verdict (emit tokens) and to seed the optimistic
    continuation.  ``drafts`` has L_max+1 entries — index n_live is the
    edge's own continuation sample at the bonus position (the
    speculation guess)."""
    slot: int
    drafts: np.ndarray            # (L_max+1,) int
    betas: np.ndarray             # (L_max+1,) f32 trajectory
    n_live: int                   # L^t — drafts actually transmitted
    packed: bytes                 # the DraftPayload on the wire
    wire_bits: float              # len(packed) * 8
    t_slm: float                  # measured draft wall-clock


@dataclasses.dataclass
class SpecDraft:
    """An uncommitted speculative draft of round t+1 (optimistic
    full-accept continuation).  Committed only when the round-t verdict
    confirms the premise; otherwise dropped on the floor — its cache
    writes sit beyond the committed position and are masked/overwritten."""
    slot: int
    in_x: int                     # premise: bonus token guess
    in_pos: int                   # premise: pos after full accept
    in_beta: float                # premise: β after full accept
    base_key: jnp.ndarray         # (2,) key consumed (replay register)
    new_key: jnp.ndarray          # (2,) key chain advance on commit
    round: PendingRound           # the speculative round's record
    # calibrated-budget EMA advance, applied only on commit (so a
    # mis-speculation leaves the scale exactly where lockstep has it)
    scale_next: Dict[int, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class DraftBatch:
    """Full-batch draft results (lockstep path + payload source)."""
    ys: dict                      # device trajectories from the scan
    drafts: np.ndarray            # (L+1, B)
    betas: np.ndarray             # (L+1, B)
    bits: np.ndarray              # (B, L) analytic per-token budget
    gap_bits: np.ndarray          # (B, L)
    dropped: np.ndarray           # (B, L+1)
    Ks: np.ndarray                # (B, L)
    live: np.ndarray              # (B, L) bool
    n_live: np.ndarray            # (B,) int
    packed: Dict[int, bytes]      # per committed slot
    t_slm: float
    # per-slot coded-size EMA advance (calibrated budget model); the
    # caller decides when it commits (draft() immediately, speculative
    # drafts only when the premise is confirmed)
    scale_next: Dict[int, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class VerifyBatch:
    """Cloud-side verify results for one call."""
    verdicts: Dict[int, wire_mod.VerdictPayload]
    T: np.ndarray                 # (B,) accepted counts
    new_token: np.ndarray         # (B,)
    rejected: np.ndarray          # (B,) bool
    p: Optional[np.ndarray]       # (B, L+1, V) when collect_theory
    t_llm: float


# ======================================================================
# Edge actor
# ======================================================================
class EdgeDraftEngine:
    """SLM drafting + SQS compression + conformal state + packing."""

    def __init__(self, dc: ModelConfig, dp, method: MethodConfig,
                 engine: EngineConfig, fmt: wire_mod.WireFormat,
                 seed: int = 0, obs: Optional[Obs] = None):
        self.dc, self.dp = dc, dp
        self.m, self.e, self.fmt = method, engine, fmt
        self.seed = seed
        self.obs = obs if obs is not None else NULL_OBS
        self.V = dc.vocab
        self.stateful = _is_stateful(dc)
        self._draft_jit = jax.jit(self._draft_round)

    # -- SQS -----------------------------------------------------------
    def _sparsify(self, q, beta, logits=None):
        m = self.m
        if m.use_kernels and m.name in ("ksqs", "csqs") and logits is not None:
            from repro.kernels import ops as kops
            if m.name == "ksqs":
                r = kops.sqs_topk(logits, m.K,
                                  temperature=self.e.temperature, ell=m.ell)
                bits = bits_mod.token_bits(self.V, float(m.K), m.ell,
                                           adaptive=False)
                bits = jnp.broadcast_to(bits, r.dropped.shape)
            else:
                r = kops.sqs_threshold(logits, beta,
                                       temperature=self.e.temperature,
                                       ell=m.ell)
                bits = bits_mod.token_bits(self.V, r.K.astype(jnp.float32),
                                           m.ell, adaptive=True)
            gap_bits = (bits_mod.gap_code_subset_bits(r.mask)
                        + bits_mod.payload_bits(r.K.astype(jnp.float32),
                                                m.ell)
                        + (jnp.ceil(jnp.log2(float(self.V)))
                           if m.name == "csqs" else 0.0))
            return r, bits, gap_bits
        if m.name == "ksqs":
            r = sqs_mod.sparsify_topk(q, m.K, m.ell)
            bits = bits_mod.token_bits(self.V, float(m.K), m.ell,
                                       adaptive=False)
            bits = jnp.broadcast_to(bits, r.dropped.shape)
        elif m.name == "csqs":
            r = sqs_mod.sparsify_threshold(q, beta, m.ell)
            bits = bits_mod.token_bits(self.V, r.K.astype(jnp.float32),
                                       m.ell, adaptive=True)
        elif m.name == "qs":
            r = sqs_mod.dense_qs(q, m.ell)
            bits = jnp.broadcast_to(bits_mod.dense_qs_bits(self.V, m.ell),
                                    r.dropped.shape)
        elif m.name == "uncompressed":
            r = sqs_mod.no_compression(q)
            bits = jnp.full(r.dropped.shape,
                            bits_mod.uncompressed_bits(self.V))
        else:
            raise ValueError(self.m.name)
        gap_bits = (bits_mod.gap_code_subset_bits(r.mask)
                    + bits_mod.payload_bits(r.K.astype(jnp.float32), m.ell)
                    + (jnp.ceil(jnp.log2(float(self.V)))
                       if m.name == "csqs" else 0.0))
        return r, bits, gap_bits

    def _draft_round(self, dp, cache, x_last, pos, beta, keys):
        """Returns drafts d_1..d_L, per-token q̂/q/bits/β trajectory and
        the advanced edge cache (+ per-step sequential-state snapshots).
        keys: (B, 2) per-row PRNG keys — each row consumes only its own
        stream (masked-batch equivalence for serving)."""
        L = self.e.L_max
        ecfg = self.dc
        seq_p = _seq_periods(ecfg)

        def step(carry, i):
            cache, tok, beta, keys, pos = carry
            # named scopes only label the ops (HLO op_name metadata), so
            # a profile can split the step's device time into the draft
            # model and SQS
            with jax.named_scope("slm"):
                logits, cache = model_mod.decode_step(ecfg, dp, tok, cache,
                                                      pos)
            with jax.named_scope("sqs"):
                keys, k1 = _split_rows(keys)
                q = sqs_mod.softmax_temp(logits, self.e.temperature)
                r, bits, gap_bits = self._sparsify(q, beta, logits=logits)
                nxt = jax.vmap(jax.random.categorical)(
                    k1, jnp.log(jnp.maximum(r.q_hat, 1e-30))
                ).astype(jnp.int32)
                new_beta = conformal.update(beta, r.dropped, self.m.alpha,
                                            self.m.eta) \
                    if self.m.name == "csqs" else beta
            snap = {p: cache["body"][p] for p in seq_p}
            ys = dict(token=nxt, q_hat=r.q_hat, q=q, bits=bits,
                      gap_bits=gap_bits, dropped=r.dropped, K=r.K,
                      beta=new_beta, snap=snap)
            return (cache, nxt, new_beta, keys, pos + 1), ys

        carry0 = (cache, x_last, beta, keys, pos)
        carry, ys = jax.lax.scan(step, carry0, jnp.arange(L + 1))
        cache = carry[0]
        return cache, ys

    # -- slot/state lifecycle ------------------------------------------
    def _alloc_state(self, B: int):
        self.B = B
        self.x_last = jnp.zeros((B,), jnp.int32)
        self.pos = jnp.zeros((B,), jnp.int32)
        self.beta = jnp.full((B,), self.m.beta0, jnp.float32)
        self.keys = jnp.stack([row_key(self.seed, b) for b in range(B)])
        # replay registers: inputs of each row's last committed draft
        self.rep_x = self.x_last
        self.rep_pos = self.pos
        self.rep_beta = self.beta
        self.rep_key = self.keys
        # per-slot negotiated codec + calibrated-budget state (EMA of
        # observed coded bits / analytic estimate, reset at admission)
        self.slot_codec = [self.fmt.codec] * B
        self.coded_scale = np.ones((B,), np.float64)

    def init_slots(self, n_slots: int, cache_len: int,
                   spec: Optional[PagedSpec]):
        self._alloc_state(n_slots)
        self.cache_len = cache_len
        self.dcache = model_mod.init_cache(self.dc, n_slots, cache_len,
                                           paged=spec)
        self._prefill_jit = jax.jit(functools.partial(
            model_mod.prefill, self.dc, cache_len=cache_len))

    def prefill_batch(self, prompts, cache_len: int):
        B, S0 = prompts.shape
        self._alloc_state(B)
        self.cache_len = cache_len
        _, self.dcache = model_mod.prefill(self.dc, self.dp,
                                           prompts[:, :-1],
                                           cache_len=cache_len)
        self.x_last = prompts[:, -1].astype(jnp.int32)
        self.pos = jnp.full((B,), S0 - 1, jnp.int32)
        self.rep_x, self.rep_pos = self.x_last, self.pos

    def admit(self, slot: int, prompt, pt_row, seed: int,
              wire_codec: Optional[str] = None):
        with self.obs.tracer.wall("edge.admit", args={"slots": [slot]}):
            S0 = int(prompt.shape[0])
            _, cache1 = self._prefill_jit(self.dp, prompt[None, :-1])
            self.dcache = model_mod.write_prefill_to_slot(
                self.dc, self.dcache, cache1, slot, pt_row, S0 - 1)
            key = row_key(seed, 0)
            self.x_last = self.x_last.at[slot].set(prompt[-1])
            self.pos = self.pos.at[slot].set(S0 - 1)
            self.beta = conformal.admit_rows(
                self.beta, jnp.arange(self.B) == slot, self.m.beta0)
            self.keys = self.keys.at[slot].set(key)
            self.rep_x = self.rep_x.at[slot].set(prompt[-1])
            self.rep_pos = self.rep_pos.at[slot].set(S0 - 1)
            self.rep_beta = self.rep_beta.at[slot].set(self.m.beta0)
            self.rep_key = self.rep_key.at[slot].set(key)
            self.slot_codec[slot] = wire_codec or self.fmt.codec
            self.coded_scale[slot] = 1.0

    def set_tables(self, pt):
        self.dcache = model_mod.set_page_tables(self.dcache, pt)

    # -- drafting ------------------------------------------------------
    def _run_draft(self, x_in, pos_in, beta_in, kd, args: dict):
        """The draft step program on the device; ``t_slm`` times the
        call and its block_until_ready, inside the ``edge.step`` span."""
        with self.obs.tracer.wall("edge.step", args=args):
            t0 = time.perf_counter()
            dcache, ys = self._draft_jit(self.dp, self.dcache, x_in, pos_in,
                                         beta_in, kd)
            jax.block_until_ready(ys["token"])
            t_slm = time.perf_counter() - t0
        self.dcache = dcache
        return ys, t_slm

    def _live_counts(self, bits: np.ndarray, mask: np.ndarray):
        """Budget-driven L^t (paper §4): stop when estimated wire bits
        exceed the budget, ≥ 1; non-committed rows transmit nothing.
        Under the calibrated budget model the analytic per-token bits
        are scaled by each slot's online coded-size ratio."""
        est = bits
        if self.e.budget_model == "calibrated":
            est = bits * self.coded_scale[:, None]
        cum = np.cumsum(est, axis=1)
        live = cum <= self.e.bit_budget
        live[:, 0] = True
        live &= mask[:, None]
        return live, live.sum(1)

    # calibrated coded-size model: EMA of observed / analytic, clamped
    # so one degenerate payload cannot wipe out the budget
    _SCALE_DECAY = 0.7
    _SCALE_CLIP = (0.25, 8.0)

    def _scale_update(self, slot: int, obs_bits: float,
                      est_bits: float) -> float:
        ratio = obs_bits / max(est_bits, 1.0)
        lo, hi = self._SCALE_CLIP
        return float(np.clip(self._SCALE_DECAY * self.coded_scale[slot]
                             + (1.0 - self._SCALE_DECAY) * ratio, lo, hi))

    def commit_scales(self, scale_next: Dict[int, float]):
        for slot, s in scale_next.items():
            self.coded_scale[slot] = s

    def _build_batch(self, ys, mask: np.ndarray, t_slm: float,
                     args: dict) -> DraftBatch:
        L = self.e.L_max
        slots = [int(s) for s in np.nonzero(mask)[0]]
        tr, mx = self.obs.tracer, self.obs.metrics
        mx.counter("edge.rows_computed").inc(self.B)
        mx.counter("edge.rows_requested").inc(len(slots))
        with tr.wall("edge.fetch", args=args):
            drafts = np.asarray(ys["token"])              # (L+1, B)
            betas = np.asarray(ys["beta"])                # (L+1, B)
            bits = np.asarray(ys["bits"][:L]).T           # (B, L)
            gap_bits = np.asarray(ys["gap_bits"][:L]).T
            dropped = np.asarray(ys["dropped"]).T         # (B, L+1)
            Ks = np.asarray(ys["K"][:L]).T
            # slice the committed rows ON DEVICE: per-slot drafts
            # (pipelined serving) must not ship the whole (L, B, V)
            # batch of distributions to host every call
            qhat_rows = {s: np.asarray(ys["q_hat"][:L, s]) for s in slots}
        packed, scale_next = {}, {}
        with tr.wall("edge.pack", args=args):
            live, n_live = self._live_counts(bits, mask)
            for slot in slots:
                payload = wire_mod.build_draft_payload(
                    self.fmt, drafts[:, slot], qhat_rows[slot],
                    betas[:, slot], int(n_live[slot]))
                data = self.fmt.pack_draft(payload,
                                           codec=self.slot_codec[slot])
                packed[slot] = data
                if self.e.budget_model == "calibrated":
                    est = float(bits[slot, :int(n_live[slot])].sum())
                    scale_next[slot] = self._scale_update(
                        slot, len(data) * 8.0, est)
        return DraftBatch(ys=ys, drafts=drafts, betas=betas, bits=bits,
                          gap_bits=gap_bits, dropped=dropped, Ks=Ks,
                          live=live, n_live=n_live, packed=packed,
                          t_slm=t_slm, scale_next=scale_next)

    def draft(self, mask: np.ndarray) -> DraftBatch:
        """One draft round, committing key-chain/replay state for rows
        in ``mask``; other rows replay their registers (bit-identical
        recompute, no state advance)."""
        args = {"slots": [int(s) for s in np.nonzero(mask)[0]]}
        with self.obs.tracer.wall("edge.prep", args=args):
            mj = jnp.asarray(mask)
            x_in = jnp.where(mj, self.x_last, self.rep_x)
            pos_in = jnp.where(mj, self.pos, self.rep_pos)
            beta_in = jnp.where(mj, self.beta, self.rep_beta)
            key_in = jnp.where(mj[:, None], self.keys, self.rep_key)
            new_keys, kd = _split_rows(key_in)
            # commit the key chain and replay registers (inputs only:
            # the step's outputs play no part in them)
            self.keys = jnp.where(mj[:, None], new_keys, self.keys)
            self.rep_x = x_in
            self.rep_pos = pos_in
            self.rep_beta = beta_in
            self.rep_key = jnp.where(mj[:, None], key_in, self.rep_key)
        ys, t_slm = self._run_draft(x_in, pos_in, beta_in, kd, args)
        batch = self._build_batch(ys, mask, t_slm, args)
        # a real draft commits its coded-size observations immediately;
        # speculative drafts carry theirs in SpecDraft.scale_next and
        # commit only when the premise is confirmed — so the EMA
        # advances exactly once per committed round in BOTH schedules
        self.commit_scales(batch.scale_next)
        return batch

    def pending_round(self, batch: DraftBatch, slot: int) -> PendingRound:
        return PendingRound(slot=slot,
                            drafts=batch.drafts[:, slot].copy(),
                            betas=batch.betas[:, slot].copy(),
                            n_live=int(batch.n_live[slot]),
                            packed=batch.packed[slot],
                            wire_bits=wire_mod.packed_bits(
                                batch.packed[slot]),
                            t_slm=batch.t_slm)

    def draft_speculative(self, slot: int, x_guess: int, pos_next: int,
                          beta_next: float) -> SpecDraft:
        """Optimistic continuation: draft round t+1 under the premise
        that every live round-t draft is accepted and the bonus token
        equals the edge's own continuation sample.  Commits NOTHING —
        the key chain advance is stored in the record and applied only
        by ``commit_speculative`` when the verdict confirms the
        premise.  (Cache writes land beyond the committed position and
        are masked / overwritten if the premise fails.)"""
        assert not self.stateful, \
            "speculative continuation requires a positional (KV) draft " \
            "cache — sequential-state drafts must run lockstep"
        onehot = np.zeros((self.B,), bool)
        onehot[slot] = True
        args = {"slots": [slot], "speculative": True}
        with self.obs.tracer.wall("edge.prep", args=args):
            mj = jnp.asarray(onehot)
            x_in = jnp.where(mj, jnp.int32(x_guess), self.rep_x)
            pos_in = jnp.where(mj, jnp.int32(pos_next), self.rep_pos)
            beta_in = jnp.where(mj, jnp.float32(beta_next), self.rep_beta)
            key_in = jnp.where(mj[:, None], self.keys, self.rep_key)
            base_key = self.keys[slot]
            new_keys, kd = _split_rows(key_in)
            new_key = new_keys[slot]
        ys, t_slm = self._run_draft(x_in, pos_in, beta_in, kd, args)
        batch = self._build_batch(ys, onehot, t_slm, args)
        return SpecDraft(slot=slot, in_x=int(x_guess), in_pos=int(pos_next),
                         in_beta=float(beta_next), base_key=base_key,
                         new_key=new_key,
                         round=self.pending_round(batch, slot),
                         scale_next=batch.scale_next)

    def commit_speculative(self, spec: SpecDraft):
        """The verdict confirmed the premise: advance the key chain and
        replay registers exactly as a real draft() commit would have."""
        s = spec.slot
        self.keys = self.keys.at[s].set(spec.new_key)
        self.rep_x = self.rep_x.at[s].set(spec.in_x)
        self.rep_pos = self.rep_pos.at[s].set(spec.in_pos)
        self.rep_beta = self.rep_beta.at[s].set(spec.in_beta)
        self.rep_key = self.rep_key.at[s].set(spec.base_key)
        self.commit_scales(spec.scale_next)

    # -- verdict application -------------------------------------------
    def apply_verdict_slot(self, slot: int,
                           verdict: wire_mod.VerdictPayload,
                           rec: PendingRound) -> List[int]:
        """Per-slot verdict (event-driven serving).  Positional caches
        need no rollback; sequential-state drafts are lockstep-only."""
        assert not self.stateful
        T = int(verdict.n_accept)
        self.pos = self.pos.at[slot].add(T + 1)
        self.x_last = self.x_last.at[slot].set(jnp.int32(verdict.new_token))
        if self.m.name == "csqs":
            self.beta = self.beta.at[slot].set(
                jnp.float32(verdict.beta_next))
        return [int(t) for t in rec.drafts[:T]] + [int(verdict.new_token)]

    def apply_verdicts_batch(self, mask: np.ndarray,
                             verdicts: Dict[int, wire_mod.VerdictPayload],
                             batch: DraftBatch) -> List[List[int]]:
        """Whole-batch verdict application (lockstep path): masked
        rollback of sequential-state snapshots, β resume from the wire,
        position/x_last advance, token emission."""
        B = self.B
        T_np = np.zeros((B,), np.int32)
        nt_np = np.zeros((B,), np.int32)
        beta_np = np.asarray(self.beta).copy()
        for slot, v in verdicts.items():
            T_np[slot] = v.n_accept
            nt_np[slot] = v.new_token
            beta_np[slot] = np.float32(v.beta_next)
        mj = jnp.asarray(mask)
        T = jnp.asarray(T_np)
        T_eff = jnp.where(mj, T, 0)
        edge_traj = ({p_: batch.ys["snap"][p_]
                      for p_ in _seq_periods(self.dc)}
                     if self.stateful else None)
        if edge_traj is not None:
            edge_traj = jax.tree.map(
                lambda a: jnp.moveaxis(a, 0, 2), edge_traj)  # (N,B,L+1,...)
        self.dcache = rollback_cache(self.dc, self.dcache, edge_traj,
                                     T_eff + 1)
        if self.m.name == "csqs":
            self.beta = jnp.where(mj, jnp.asarray(beta_np), self.beta)
        self.pos = self.pos + jnp.where(mj, T + 1, 0)
        self.x_last = jnp.where(mj, jnp.asarray(nt_np), self.x_last)
        emitted = [[] for _ in range(B)]
        for slot in verdicts:
            emitted[slot] = ([int(t) for t in batch.drafts[:T_np[slot],
                                                           slot]]
                             + [int(nt_np[slot])])
        return emitted


# ======================================================================
# Cloud actor
# ======================================================================
class CloudVerifyEngine:
    """LLM parallel verification against the transmitted q̂."""

    def __init__(self, tc: ModelConfig, tp, method: MethodConfig,
                 engine: EngineConfig, fmt: wire_mod.WireFormat,
                 seed: int = 0, obs: Optional[Obs] = None):
        self.tc, self.tp = tc, tp
        self.m, self.e, self.fmt = method, engine, fmt
        self.seed = seed
        self.obs = obs if obs is not None else NULL_OBS
        self.V = tc.vocab
        self.stateful = _is_stateful(tc)
        self._verify_jit = jax.jit(self._verify_round)

    def _verify_round(self, tp, cache, tokens_in, pos, q_hat, live, key):
        """tokens_in: (B, L+1) = [x_last, d_1..d_L]."""
        with jax.named_scope("llm"):
            if self.stateful:
                logits, cache, traj = model_mod.extend_step(
                    self.tc, tp, tokens_in, cache, pos, collect_traj=True)
            else:
                logits, cache = model_mod.extend_step(self.tc, tp,
                                                      tokens_in, cache, pos)
                traj = None
        with jax.named_scope("verify"):
            p = sqs_mod.softmax_temp(logits, self.e.temperature)
            res = verify_mod.verify(key, tokens_in[:, 1:], q_hat, p, live)
        return res, p, cache, traj                # p: (B, L+1, V)

    # -- slot/state lifecycle ------------------------------------------
    def _alloc_state(self, B: int):
        L = self.e.L_max
        self.B = B
        self.x_last = jnp.zeros((B,), jnp.int32)
        self.pos = jnp.zeros((B,), jnp.int32)
        self.keys = jnp.stack([cloud_row_key(self.seed, b)
                               for b in range(B)])
        # replay registers: inputs of each row's last committed verify
        self.rep_tokens = jnp.zeros((B, L), jnp.int32)
        self.rep_qhat = jnp.zeros((B, L, self.V), jnp.float32)
        self.rep_live = jnp.zeros((B, L), jnp.bool_)
        self.rep_x = self.x_last
        self.rep_pos = self.pos
        self.rep_key = self.keys
        self.slot_codec = [self.fmt.codec] * B   # negotiated per admit

    def init_slots(self, n_slots: int, cache_len: int,
                   spec: Optional[PagedSpec]):
        self._alloc_state(n_slots)
        self.cache_len = cache_len
        self.tcache = model_mod.init_cache(self.tc, n_slots, cache_len,
                                           paged=spec)
        self._prefill_jit = jax.jit(functools.partial(
            model_mod.prefill, self.tc, cache_len=cache_len))

    def prefill_batch(self, prompts, cache_len: int):
        B, S0 = prompts.shape
        self._alloc_state(B)
        self.cache_len = cache_len
        _, self.tcache = model_mod.prefill(self.tc, self.tp,
                                           prompts[:, :-1],
                                           cache_len=cache_len)
        self.x_last = prompts[:, -1].astype(jnp.int32)
        self.pos = jnp.full((B,), S0 - 1, jnp.int32)
        self.rep_x, self.rep_pos = self.x_last, self.pos

    def admit(self, slot: int, prompt, pt_row, seed: int,
              wire_codec: Optional[str] = None):
        with self.obs.tracer.wall("cloud.admit", args={"slots": [slot]}):
            S0 = int(prompt.shape[0])
            _, cache1 = self._prefill_jit(self.tp, prompt[None, :-1])
            self.tcache = model_mod.write_prefill_to_slot(
                self.tc, self.tcache, cache1, slot, pt_row, S0 - 1)
            self.slot_codec[slot] = wire_codec or self.fmt.codec
            key = cloud_row_key(seed, 0)
            self.x_last = self.x_last.at[slot].set(prompt[-1])
            self.pos = self.pos.at[slot].set(S0 - 1)
            self.keys = self.keys.at[slot].set(key)
            self.rep_tokens = self.rep_tokens.at[slot].set(0)
            self.rep_qhat = self.rep_qhat.at[slot].set(0.0)
            self.rep_live = self.rep_live.at[slot].set(False)
            self.rep_x = self.rep_x.at[slot].set(prompt[-1])
            self.rep_pos = self.rep_pos.at[slot].set(S0 - 1)
            self.rep_key = self.rep_key.at[slot].set(key)

    def set_tables(self, pt):
        self.tcache = model_mod.set_page_tables(self.tcache, pt)

    # -- verification --------------------------------------------------
    def verify(self, mask: np.ndarray,
               payloads: Dict[int, wire_mod.DraftPayload],
               collect_p: bool = False) -> VerifyBatch:
        """Verify the rows in ``mask`` against their unpacked payloads;
        other rows replay their registers.  Commits cloud mirrors
        (pos/x_last), the key chain, the (rolled-back) target cache and
        the replay registers for ``mask`` rows, and packs one verdict
        per payload — including the Alg.-1 β backtrack computed from
        the wire trajectory."""
        B, L = self.B, self.e.L_max
        tr, mx = self.obs.tracer, self.obs.metrics
        args = {"slots": sorted(payloads)}
        if mx.enabled:
            mx.counter("wire.payloads").inc(len(payloads))
            mx.counter("wire.live_drafts").inc(
                sum(p.n_drafts for p in payloads.values()))
            mx.counter("wire.support_tokens").inc(
                sum(len(s) for p in payloads.values() for s in p.supports))
        with tr.wall("cloud.upload", args=args):
            tok_np = np.zeros((B, L), np.int32)
            qhat_np = np.zeros((B, L, self.V), np.float32)
            live_np = np.zeros((B, L), bool)
            for slot, p in payloads.items():
                assert mask[slot], f"payload for non-committed slot {slot}"
                tok_np[slot], qhat_np[slot], live_np[slot] = \
                    wire_mod.draft_arrays(self.fmt, p)
            mj = jnp.asarray(mask)
            tokens = jnp.where(mj[:, None], jnp.asarray(tok_np),
                               self.rep_tokens)
            qhat = jnp.where(mj[:, None, None], jnp.asarray(qhat_np),
                             self.rep_qhat)
            live = jnp.where(mj[:, None], jnp.asarray(live_np),
                             self.rep_live)
            x_in = jnp.where(mj, self.x_last, self.rep_x)
            pos_in = jnp.where(mj, self.pos, self.rep_pos)
            key_in = jnp.where(mj[:, None], self.keys, self.rep_key)
            new_keys, kv = _split_rows(key_in)
            tokens_in = jnp.concatenate([x_in[:, None], tokens], axis=1)
        with tr.wall("cloud.step", args=args):
            t0 = time.perf_counter()
            res, p_dists, tcache, traj = self._verify_jit(
                self.tp, self.tcache, tokens_in, pos_in, qhat, live, kv)
            jax.block_until_ready(res.n_accept)
            t_llm = time.perf_counter() - t0
        with tr.wall("cloud.commit", args=args):
            T = res.n_accept
            T_eff = jnp.where(mj, T, 0)
            self.tcache = rollback_cache(self.tc, tcache, traj, T_eff + 1)
            self.pos = jnp.where(mj, pos_in + T + 1, self.pos)
            self.x_last = jnp.where(mj, res.new_token, self.x_last)
            self.keys = jnp.where(mj[:, None], new_keys, self.keys)
            self.rep_tokens = tokens
            self.rep_qhat = qhat
            self.rep_live = live
            self.rep_x = x_in
            self.rep_pos = pos_in
            self.rep_key = jnp.where(mj[:, None], key_in, self.rep_key)
            T_np = np.asarray(T)
            nt_np = np.asarray(res.new_token)
            rej_np = np.asarray(res.rejected)
            verdicts = {
                slot: wire_mod.VerdictPayload(
                    n_accept=int(T_np[slot]),
                    new_token=int(nt_np[slot]),
                    beta_next=conformal.backtrack_wire(p.betas,
                                                       int(T_np[slot])))
                for slot, p in payloads.items()
            }
            return VerifyBatch(verdicts=verdicts, T=T_np, new_token=nt_np,
                               rejected=rej_np,
                               p=np.asarray(p_dists) if collect_p else None,
                               t_llm=t_llm)


# ======================================================================
# Edge-side base: slot lifecycle + per-slot round steps
# ======================================================================
class EdgeEngineBase:
    """Everything the serving loops need from the EDGE side of the link:
    format negotiation, the draft actor, the slot lifecycle, per-slot
    drafting, speculative continuation and verdict application.

    Two engines extend it: the in-process ``EdgeCloudEngine`` below
    (adds the cloud actor, the mirrored page allocator hooks and the
    lockstep ``run_round``) and the socket-transport client engine
    (``repro.serve.net.EdgeTransportEngine`` — its verify side lives in
    another PROCESS behind ``core.transport``).  Sharing this class is
    what makes the simulated and socketed paths bit-identical BY
    CONSTRUCTION: there is exactly one implementation of every
    token-affecting edge step, and subclasses only override how the
    verify peer is reached (``_init_peer_slots`` / ``_admit_peer`` /
    ``_push_tables``).

    ``obs`` (default ``NULL_OBS``) reaches every actor: wall spans
    around the step programs and the host work between them, and the
    wire and row counters.  ``ServeSession`` hands its own ``Obs`` in,
    so one handle covers the serving loop and the engine."""

    def __init__(self, draft_cfg: ModelConfig, draft_params,
                 method: MethodConfig, engine: EngineConfig,
                 channel: channel_mod.ChannelConfig, seed: int,
                 obs: Optional[Obs] = None):
        self.dc, self.dp = draft_cfg, draft_params
        self.m, self.e, self.ch = method, engine, channel
        self.seed = seed
        self.V = draft_cfg.vocab
        assert engine.wire_codec in wire_mod.CODECS, engine.wire_codec
        assert engine.budget_model in ("analytic", "calibrated"), \
            engine.budget_model
        self.fmt = wire_mod.WireFormat(
            V=self.V, ell=method.ell, L_max=engine.L_max,
            mode="raw" if method.name == "uncompressed" else "lattice",
            codec=engine.wire_codec)
        self.edge = EdgeDraftEngine(draft_cfg, draft_params, method,
                                    engine, self.fmt, seed, obs)
        self.peer_stateful = False    # does the verify-side model carry
        self.paged = False            # recurrent state? (subclasses set)
        self.alloc: Optional[PageAllocator] = None

    # -- observability ------------------------------------------------
    def _actors(self):
        return (self.edge,)

    @property
    def obs(self) -> Obs:
        return self.edge.obs

    @obs.setter
    def obs(self, obs: Obs):
        for actor in self._actors():
            actor.obs = obs

    # -- state passthroughs (tests/benchmarks read these) ---------------
    @property
    def beta(self):
        return self.edge.beta

    @property
    def pos(self):
        return self.edge.pos

    @property
    def x_last(self):
        return self.edge.x_last

    @property
    def dcache(self):
        return self.edge.dcache

    # ------------------------------------------------------------------
    # Session-slot API (continuous batching — repro.serve)
    # ------------------------------------------------------------------
    def init_slots(self, n_slots: int, cache_len: int,
                   page_size: int = 0, n_pages: Optional[int] = None):
        """Allocate ``n_slots`` empty session slots with per-slot cache
        capacity ``cache_len``.  Slots are filled by admit_slot and freed
        by release_slot; rounds only advance committed slots.

        ``page_size > 0`` switches eligible attention layers to the PAGED
        layout: one shared pool of ``n_pages`` pages per layer (default:
        slots × pages-per-slot, i.e. the dense footprint) instead of a
        dense per-slot cache.  The edge and cloud actors mirror ONE
        allocator (identical admit/grow/shrink sequences on both sides
        of the link keep their pools in lockstep), so HBM holds the sum
        of ACTUAL request lengths and ``n_pages`` caps concurrency."""
        assert self.dc.n_encoder_layers == 0, \
            "serving slots do not support encoder-decoder architectures"
        self.B = n_slots
        self.paged = page_size > 0
        spec = None
        if self.paged:
            assert cache_len % page_size == 0, (cache_len, page_size)
            maxp = cache_len // page_size
            n_pages = n_pages if n_pages is not None else n_slots * maxp
            assert n_pages >= maxp, \
                "pool must fit at least one worst-case request"
            spec = PagedSpec(page_size=page_size, n_pages=n_pages,
                             max_pages_per_slot=maxp)
            self.alloc = PageAllocator(n_pages, page_size, n_slots, maxp)
        else:
            self.alloc = None
        self.cache_len = cache_len
        self.edge.init_slots(n_slots, cache_len, spec)
        self._init_peer_slots(n_slots, cache_len, spec)
        self.active = np.zeros((n_slots,), bool)
        self.out_tokens = [[] for _ in range(n_slots)]

    def _init_peer_slots(self, n_slots: int, cache_len: int,
                         spec: Optional[PagedSpec]):
        """Hook: mirror the slot allocation on the verify side (the
        in-process cloud actor, or a remote server's own init)."""

    # -- paged-pool bookkeeping (host side; no-ops in dense mode) -------
    def _device_tables(self):
        return sanitize_page_table(self.alloc.table, self.alloc.n_pages)

    def _push_tables(self):
        self.edge.set_tables(self._device_tables())

    def pages_needed(self, n_tokens: int) -> int:
        assert self.paged
        return self.alloc.pages_needed(n_tokens)

    def free_pages(self) -> int:
        assert self.paged
        return self.alloc.free_pages

    def ensure_round_capacity(self) -> bool:
        """Grow every active slot's page table to cover this round's
        draft window (pos + L_max + 1 positions).  Returns False on pool
        exhaustion WITHOUT rolling back other slots' growth — the
        serving layer preempts a request and retries."""
        if not self.paged:
            return True
        pos = np.asarray(self.pos)
        for slot in range(self.B):
            if not self.active[slot]:
                continue
            if not self.alloc.ensure(slot,
                                     int(pos[slot]) + self.e.L_max + 1):
                return False
        return True

    def ensure_slot_capacity(self, slot: int, n_tokens: int) -> bool:
        """Per-slot page growth (event-driven serving)."""
        if not self.paged:
            return True
        return self.alloc.ensure(slot, n_tokens)

    def admit_slot(self, slot: int, prompt, seed: int,
                   wire_codec: Optional[str] = None):
        """Prefill ``prompt`` (1-D int32, ≥ 2 tokens) into ``slot`` on
        BOTH sides of the link.  The request's RNG/β/position state
        restarts from scratch — other slots' caches and controller
        state are untouched (their leaves are only re-packed, not
        re-computed).  ``wire_codec`` overrides the link's negotiated
        codec version for this request (both actors store the same
        negotiation, so nothing version-related rides the wire).

        Capacity contract: each round writes draft KV up to pos + L_max,
        and pos advances with every accepted token, so the CALLER must
        bound generation length such that prompt + generated + L_max + 1
        fits in cache_len (ServeSession enforces this from the request's
        max_new_tokens; the engine can only check the first round)."""
        prompt = jnp.asarray(prompt, jnp.int32)
        assert prompt.ndim == 1 and prompt.shape[0] >= 2
        assert not self.active[slot], f"slot {slot} still occupied"
        S0 = int(prompt.shape[0])
        assert S0 + self.e.L_max + 1 <= self.cache_len, \
            f"prompt ({S0}) + draft window ({self.e.L_max + 1}) exceeds " \
            f"slot capacity {self.cache_len}"
        assert wire_codec is None or wire_codec in wire_mod.CODECS, \
            wire_codec
        pt_row = None
        if self.paged:
            if not self.alloc.admit(slot, S0 - 1):
                raise RuntimeError(
                    f"page pool exhausted admitting slot {slot} "
                    f"({self.alloc.free_pages} free); the scheduler "
                    f"should gate admissions on free_pages()")
            pt_row = self._device_tables()[slot]
        self.edge.admit(slot, prompt, pt_row, seed, wire_codec=wire_codec)
        self._admit_peer(slot, prompt, pt_row, seed, wire_codec)
        self.active[slot] = True
        self.out_tokens[slot] = []

    def _admit_peer(self, slot: int, prompt, pt_row, seed: int,
                    wire_codec: Optional[str]):
        """Hook: mirror the admission on the verify side."""

    def release_slot(self, slot: int):
        """Evict a finished (or preempted) request.  Dense mode: the
        slot's cache is dead weight until the next admit overwrites it.
        Paged mode: every page returns to the pool immediately."""
        self.active[slot] = False
        if self.paged:
            self.alloc.release(slot)

    # ------------------------------------------------------------------
    # Per-slot round steps (event-driven serving — repro.serve.events)
    # ------------------------------------------------------------------
    def draft_slots(self, slots: List[int]) -> Dict[int, PendingRound]:
        """Draft one round for ``slots`` (each on its own edge device);
        returns the packed uplink message + emission record per slot."""
        mask = np.zeros((self.B,), bool)
        mask[list(slots)] = True
        if self.paged:
            pos = np.asarray(self.pos)
            for s in slots:
                ok = self.alloc.ensure(s, int(pos[s]) + self.e.L_max + 1)
                assert ok, "page pool exhausted — the event loop's " \
                    "worst-case admission gate should prevent this"
            self._push_tables()
        batch = self.edge.draft(mask)
        return {s: self.edge.pending_round(batch, s) for s in slots}

    def draft_speculative_slot(self, slot: int,
                               rec: PendingRound) -> Optional[SpecDraft]:
        """Optimistic continuation for ``slot`` while its round is in
        flight.  Returns None when speculation is pointless or unsafe
        (window would exceed slot capacity / page pool)."""
        if self.edge.stateful or self.peer_stateful:
            return None
        n = rec.n_live
        pos_next = int(np.asarray(self.pos)[slot]) + n + 1
        if pos_next + self.e.L_max + 1 > self.cache_len:
            return None
        if self.paged:
            if not self.alloc.ensure(slot, pos_next + self.e.L_max + 1):
                return None
            self._push_tables()
        return self.edge.draft_speculative(
            slot, int(rec.drafts[n]), pos_next, float(rec.betas[n]))

    def commit_speculative(self, spec: SpecDraft):
        with self.obs.tracer.wall("edge.apply", args={"slots": [spec.slot]}):
            self.edge.commit_speculative(spec)

    def spec_premise_holds(self, spec: SpecDraft, rec: PendingRound,
                           verdict: wire_mod.VerdictPayload) -> bool:
        """Was the optimistic continuation drafted from the true state?
        (β agreement is implied: accept-all backtracks to the same
        trajectory entry the speculation resumed from.)"""
        return (verdict.n_accept == rec.n_live
                and verdict.new_token == spec.in_x)

    def unpack_verdict_slot(self, slot: int,
                            data: bytes) -> wire_mod.VerdictPayload:
        with self.obs.tracer.wall("verdict.unpack", args={"slots": [slot]}):
            return self.fmt.unpack_verdict(
                data, codec=self.edge.slot_codec[slot])

    def unpack_verdict_batch(self, data: bytes):
        """Edge side: decode a cell's frame back to ascending-slot
        (slot, VerdictPayload) pairs."""
        with self.obs.tracer.wall("verdict.unpack"):
            return self.fmt.unpack_verdict_batch(data, self.B)

    def apply_verdict_slot(self, slot: int,
                           verdict: wire_mod.VerdictPayload,
                           rec: PendingRound,
                           shrink: bool = True) -> List[int]:
        """Edge side of verdict arrival: emit tokens, resume β, shrink
        the slot's pages past the kept length.  ``shrink=False`` keeps
        the grown window — the event loop passes it when a confirmed
        speculative round's draft KV lives in those pages."""
        with self.obs.tracer.wall("edge.apply", args={"slots": [slot]}):
            emitted = self.edge.apply_verdict_slot(slot, verdict, rec)
            self.out_tokens[slot].extend(emitted)
            if self.paged and shrink:
                self.alloc.shrink(slot, int(np.asarray(self.pos)[slot]))
        return emitted


# ======================================================================
# Facade: slot lifecycle + lockstep rounds over the wire
# ======================================================================
class EdgeCloudEngine(EdgeEngineBase):
    """Owns the two actors, the slot lifecycle and the (mirrored) page
    allocator; moves packed payloads between them.  ``run_round`` is the
    lockstep schedule (draft ∥ … then verify then feedback — the paper's
    Algorithm 1); the event-driven pipelined schedule lives in
    ``repro.serve.events`` and drives the per-slot methods instead."""

    def __init__(self, draft_cfg: ModelConfig, draft_params,
                 target_cfg: ModelConfig, target_params,
                 method: MethodConfig, engine: EngineConfig = EngineConfig(),
                 channel: channel_mod.ChannelConfig =
                 channel_mod.ChannelConfig(),
                 seed: int = 0, obs: Optional[Obs] = None):
        assert draft_cfg.vocab == target_cfg.vocab, "shared vocabulary"
        super().__init__(draft_cfg, draft_params, method, engine,
                         channel, seed, obs)
        self.tc, self.tp = target_cfg, target_params
        self.cloud = CloudVerifyEngine(target_cfg, target_params, method,
                                       engine, self.fmt, seed, obs)
        self._target_stateful = self.cloud.stateful
        self.peer_stateful = self.cloud.stateful

    def _actors(self):
        return (self.edge, self.cloud)

    @property
    def tcache(self):
        return self.cloud.tcache

    # ------------------------------------------------------------------
    def prefill(self, prompts):
        """prompts: (B, S0) int32.  Prepares both actors; the last prompt
        token becomes x_last (first token the draft loop processes)."""
        B, S0 = prompts.shape
        self.B = B
        self.paged = False
        self.alloc = None
        total = S0 + 4096  # cache capacity headroom
        self.edge.prefill_batch(prompts, total)
        self.cloud.prefill_batch(prompts, total)
        self.active = np.ones((B,), bool)
        self.out_tokens = [[] for _ in range(B)]

    # -- verify-side hooks (the in-process cloud actor) -----------------
    def _init_peer_slots(self, n_slots: int, cache_len: int,
                         spec: Optional[PagedSpec]):
        assert self.tc.n_encoder_layers == 0, \
            "serving slots do not support encoder-decoder architectures"
        self.cloud.init_slots(n_slots, cache_len, spec)

    def _admit_peer(self, slot: int, prompt, pt_row, seed: int,
                    wire_codec: Optional[str]):
        self.cloud.admit(slot, prompt, pt_row, seed, wire_codec=wire_codec)

    def _push_tables(self):
        pt = self._device_tables()
        self.edge.set_tables(pt)
        self.cloud.set_tables(pt)

    def verify_slots(self, packed: Dict[int, bytes]) -> VerifyBatch:
        """Cloud side of one round for the slots whose payloads arrived:
        unpack (with each slot's negotiated codec), verify, pack
        verdicts."""
        mask = np.zeros((self.B,), bool)
        mask[list(packed)] = True
        if self.paged:
            self._push_tables()
        payloads = self._unpack_drafts(packed)
        return self.cloud.verify(mask, payloads)

    def _unpack_drafts(self, packed: Dict[int, bytes]
                       ) -> Dict[int, wire_mod.DraftPayload]:
        with self.obs.tracer.wall("cloud.unpack",
                                  args={"slots": sorted(packed)}):
            return wire_mod.unpack_drafts(
                self.fmt, packed,
                codecs={s: self.cloud.slot_codec[s] for s in packed})

    # -- per-slot verdict codec (the downlink mirror of the uplink
    #    negotiation; events.py and run_round both route through these)
    def pack_verdict_slot(self, slot: int,
                          v: wire_mod.VerdictPayload) -> bytes:
        with self.obs.tracer.wall("verdict.pack", args={"slots": [slot]}):
            return self.fmt.pack_verdict(v,
                                         codec=self.cloud.slot_codec[slot])

    # -- verdict BATCHING (one coded downlink frame per cell).  A frame
    #    serves many requests at once, so its codec is the LINK's
    #    negotiated version (EngineConfig.wire_codec), never a
    #    per-request override — both actors resolve it identically from
    #    static config, so nothing version-related rides the wire.
    def pack_verdict_batch(self, verdicts: Dict[int,
                                                wire_mod.VerdictPayload]
                           ) -> bytes:
        """Cloud side: coalesce one cell's verdicts (ascending slot
        order — the deterministic frame order both ends rely on) into
        one downlink frame."""
        items = sorted(verdicts.items())
        with self.obs.tracer.wall("verdict.pack",
                                  args={"slots": [s for s, _ in items]}):
            return self.fmt.pack_verdict_batch(items, self.B)

    # ------------------------------------------------------------------
    def run_round(self, verdict_groups: Optional[List[List[int]]] = None):
        """One lockstep SD batch over the ACTIVE rows, through the wire.
        Returns a metrics dict (host values).  Inactive slots still flow
        through the compute (static shapes) but are masked out of
        budgets, rollback depth, state advancement and every reported
        statistic.

        ``verdict_groups`` (multi-cell serving with verdict batching):
        lists of slots sharing a downlink — the cloud coalesces each
        group's verdicts into ONE coded frame, and the edge applies the
        FRAME-decoded verdicts, so the bytes the serving clock charges
        are exactly the bytes the edge consumed.  The per-slot packed
        sizes are still reported (``verdict_bits_row``) as the unbatched
        reference the cell study compares against."""
        L = self.e.L_max
        active = np.asarray(self.active, bool)
        n_active = max(int(active.sum()), 1)
        if self.paged:
            if not self.ensure_round_capacity():
                raise RuntimeError(
                    "page pool exhausted growing the round's draft "
                    "windows; preempt a request (ServeSession does) "
                    "before run_round")
            self._push_tables()

        db = self.edge.draft(active)
        # --- the uplink: packed bytes cross, the cloud decodes ---------
        payloads = self._unpack_drafts(db.packed)
        wire_bits_row = np.zeros((self.B,), np.float64)
        for slot, data in db.packed.items():
            wire_bits_row[slot] = wire_mod.packed_bits(data)
        vb = self.cloud.verify(active, payloads,
                               collect_p=self.e.collect_theory)
        # --- the downlink: packed verdicts cross back ------------------
        verdict_packed = {s: self.pack_verdict_slot(s, v)
                          for s, v in vb.verdicts.items()}
        verdict_bits_row = np.zeros((self.B,), np.float64)
        for slot, data in verdict_packed.items():
            verdict_bits_row[slot] = wire_mod.packed_bits(data)
        verdict_frames = []
        if verdict_groups is None:
            verdicts = {s: self.unpack_verdict_slot(s, b)
                        for s, b in verdict_packed.items()}
        else:
            # one coded frame per group; the edge decodes the frame —
            # round-trips are exact, so streams match the unbatched path
            verdicts = {}
            grouped = [s for g in verdict_groups for s in g]
            assert sorted(grouped) == sorted(vb.verdicts), \
                "verdict_groups must cover exactly the active slots"
            for group in verdict_groups:
                items = {s: vb.verdicts[s] for s in group}
                if not items:
                    continue
                frame = self.pack_verdict_batch(items)
                verdicts.update(dict(self.unpack_verdict_batch(frame)))
                verdict_frames.append(
                    {"slots": sorted(items),
                     "bits": wire_mod.packed_bits(frame)})
        emitted = self.edge.apply_verdicts_batch(active, verdicts, db)
        for b in range(self.B):
            self.out_tokens[b].extend(emitted[b])
        if self.paged:
            # speculative rollback, memory side: pages covering only the
            # rejected draft tail (positions >= new pos) go back to the
            # pool; the next round's ensure re-grows as needed.
            pos_np = np.asarray(self.pos)
            for slot in range(self.B):
                if active[slot]:
                    self.alloc.shrink(slot, int(pos_np[slot]))

        T_np = vb.T
        live_np = db.live
        bits_row = (db.bits * live_np).sum(1)              # (B,)
        gap_bits_row = (db.gap_bits * live_np).sum(1)
        live_bits = float(bits_row.sum() / n_active)
        live_gap_bits = float(gap_bits_row.sum() / n_active)
        wire_bits = float(wire_bits_row.sum() / n_active)
        t_up = channel_mod.uplink_time(self.ch, wire_bits)
        t_down = channel_mod.downlink_time(
            self.ch, float(verdict_bits_row.max()) if active.any()
            else channel_mod.feedback_bits(L, self.V))
        metrics = {
            "n_accept": np.where(active, T_np, 0),
            "rejected": vb.rejected & active,
            "L_live": live_np.sum(1),
            "bits": live_bits,
            "gap_bits": live_gap_bits,
            "bits_row": bits_row,
            "gap_bits_row": gap_bits_row,
            "wire_bits": wire_bits,
            "wire_bits_row": wire_bits_row,
            "verdict_bits_row": verdict_bits_row,
            "verdict_frames": verdict_frames,
            "active": active.copy(),
            "emitted": emitted,
            "K_mean": float((db.Ks * live_np).sum()
                            / max(live_np.sum(), 1)),
            "dropped_mean": float(db.dropped[active, :L].mean())
            if active.any() else 0.0,
            "t_slm": db.t_slm, "t_up": t_up, "t_llm": vb.t_llm,
            "t_down": t_down,
            "t_total": db.t_slm + t_up + vb.t_llm + t_down,
            "tokens_out": np.where(active, 1 + T_np, 0),
            # pre-round conformal thresholds per row — the beta
            # trajectory obs.decomp tracks across rounds
            "beta_row": db.betas[0].copy(),
        }
        if self.paged:
            metrics["pages_in_use"] = self.alloc.pages_in_use
            metrics["free_pages"] = self.alloc.free_pages
            metrics["peak_pages_in_use"] = self.alloc.peak_in_use
        if self.e.collect_theory:
            metrics["q"] = np.asarray(db.ys["q"][:L].swapaxes(0, 1))
            metrics["q_hat"] = np.asarray(
                db.ys["q_hat"][:L].swapaxes(0, 1))
            metrics["p"] = vb.p
            metrics["dropped_seq"] = db.dropped
            metrics["K_seq"] = db.Ks
            metrics["live_seq"] = live_np.copy()
        return metrics

    # ------------------------------------------------------------------
    def run(self, prompts, n_rounds: int):
        self.prefill(jnp.asarray(prompts, jnp.int32))
        rounds = [self.run_round() for _ in range(n_rounds)]
        return rounds, self.out_tokens


def summarize(rounds):
    """Aggregate per-round metrics into the paper's two headline numbers:
    average end-to-end latency per batch and resampling rate."""
    resample = np.mean([r["rejected"].mean() for r in rounds])
    lat = np.mean([r["t_total"] for r in rounds])
    toks = np.sum([r["tokens_out"].mean() for r in rounds])
    return {
        "resampling_rate": float(resample),
        "latency_per_batch_s": float(lat),
        "latency_per_token_s": float(lat * len(rounds) / max(toks, 1)),
        "bits_per_batch": float(np.mean([r["bits"] for r in rounds])),
        "gap_bits_per_batch": float(np.mean([r["gap_bits"]
                                             for r in rounds])),
        "wire_bits_per_batch": float(np.mean([r.get("wire_bits", 0.0)
                                              for r in rounds])),
        "accept_rate": float(np.mean(
            [r["n_accept"].mean() / max(r["L_live"].mean(), 1)
             for r in rounds])),
        "mean_K": float(np.mean([r["K_mean"] for r in rounds])),
        "tokens_per_batch": float(np.mean([r["tokens_out"].mean()
                                           for r in rounds])),
    }
