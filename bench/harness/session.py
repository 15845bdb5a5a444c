"""The served run: the program's pipelined edge-cloud serving path,
driven through its public API, with a measured window on the wall clock.

    engine  = EdgeCloudEngine(draft, target, method, engine, channel)
    session = ServeSession(engine, ServeConfig(pipeline="pipelined",
                                               speculate=True, ...))
    session.run_trace(trace)

What this module adds around it, all from the benchmark's side:

* set-up: weights from the seed (each model's kind, harness.kinds),
  every prefill bucket, the draft, speculative-draft, verify and verdict
  paths warmed once;
* the window: it opens at the first engine call after every slot has
  been filled and one delivery per slot has been made since, and closes at the first engine call ``seconds`` of wall time
  later.  A traced run profiles its whole window, which then lasts the
  cell's ``trace_seconds`` when that is shorter.  Deliveries are
  counted by a ``Request`` subclass whose ``add_tokens`` records
  (serving clock, wall clock, tokens);
* the drain: at close, arrivals not yet due are withdrawn (their length
  is set so that admission rejects them) and every request in flight
  finishes at its next delivery, so the run ends within a round or two;
* records for the metrics: one line per engine call (wall span, the
  program's own t_slm / t_llm, slots, context lengths), the uplink
  counters of the program's ``SharedLink`` at open and close; in a
  traced run, the program's own ``Obs`` (handed to its ``ServeSession``)
  with its counters at open and close, and the two step programs'
  compiled text (``program_texts``), which names their ops' scopes;
* the audit for ``correct``, in two parts:
  - every served position: for every real draft call and every verify
    call in the window, the first row of the step's distribution (the
    draft's q and the target's p at the position of the request's last
    served token) with that token and position, copied to the host
    asynchronously;
  - a reservoir sample, drawn from the seed, of the window's real draft
    calls: the draft step's outputs for all L+1 positions (q, q-hat,
    tokens, beta) and the outputs of the verify call that carries its
    payload (p, q-hat as decoded, tokens, live mask, per-row key, accept
    count, new token), for the SQS, wire and sampler checks.
  Both read the two jitted step programs' outputs through the engine's
  ``_draft_jit`` / ``_verify_jit`` attributes (the program has no public
  hook for them); they call them exactly as the program does and add no
  device work beyond the slices.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from harness import traffic as traffic_mod

# serving settings that no cell varies yet
QUEUE_CAP = 32          # requests waiting for a slot before arrivals are refused
N_RADIO_CELLS = 1       # one shared uplink and downlink
SETTLE_ROUNDS = 1       # deliveries per slot between the slots filling and the window


def _abstract(x):
    """Shape, dtype and, for an array placed on a device, its sharding:
    what the jit cache keys a call on."""
    committed = getattr(x, "committed", False)
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                sharding=x.sharding if committed else None)


# -- device-side row slicers (compiled in set-up, reused in the window) --
@jax.jit
def _draft_rows(q, q_hat, token, beta, K, x_in, pos_in, beta_in, row):
    return (q[:, row], q_hat[:, row], token[:, row], beta[:, row],
            K[:, row], x_in[row], pos_in[row], beta_in[row])


@jax.jit
def _draft_row0(q, x_in, pos_in, row):
    return q[0, row], x_in[row], pos_in[row]


@jax.jit
def _verify_row0(tokens_in, pos, p, row):
    return p[row, 0], tokens_in[row, 0], pos[row]


@jax.jit
def _verify_rows(tokens_in, pos, q_hat, live, key, p, n_accept, new_token,
                 row):
    return (tokens_in[row], pos[row], q_hat[row], live[row], key[row],
            p[row], n_accept[row], new_token[row])


class ServedRun:
    """One run of a cell: build, warm, serve through the window, drain."""

    def __init__(self, cell: dict, traffic: dict, target, draft, seed: int,
                 seconds: float, clock, trace_dir: Optional[str] = None,
                 trace_seconds: float = 0.0, fault=None, obs=None):
        from repro.core import EdgeCloudEngine, EngineConfig, MethodConfig
        from repro.core.channel import ChannelConfig
        from repro.serve import ServeConfig, ServeSession
        self.cell, self.traffic = cell, traffic
        self.target, self.draft = target, draft     # harness.kinds.Model
        # a traced run traces its whole window, which is then the
        # shorter of the two lengths: the profiler stops after the close
        self.seconds = min(seconds, trace_seconds) if trace_dir else seconds
        self.clock = clock                 # compile-event clock
        self.trace_dir = trace_dir
        self.rng = np.random.default_rng([seed, 7])
        m, e, ch = cell["method"], cell["engine"], cell["channel"]
        self.B = cell["slots"]
        self.L = e["L_max"]
        self.cache_len = (max(traffic["prompt_buckets"]) + traffic["out_max"]
                          + self.L + 8)
        self.engine = EdgeCloudEngine(
            draft.cfg, draft.kind.to_program(draft.cfg, draft.w), target.cfg,
            target.kind.to_program(target.cfg, target.w),
            MethodConfig(m["name"], K=m["K"], ell=m["ell"],
                         alpha=m["alpha"], eta=m["eta"]),
            EngineConfig(L_max=self.L, bit_budget=e["bit_budget"],
                         temperature=e["temperature"],
                         wire_codec=e["wire_codec"]),
            ChannelConfig(uplink_bps=ch["uplink_bps"],
                          downlink_bps=ch["downlink_bps"]),
            seed=int(self.rng.integers(0, 2**31 - 1)))
        self.session = ServeSession(self.engine, ServeConfig(
            max_batch=self.B, queue_cap=QUEUE_CAP,
            cache_len=self.cache_len, pipeline="pipelined",
            speculate=True, n_cells=N_RADIO_CELLS), obs=obs)
        self.obs = obs
        self.obs_open = self.obs_close = None  # its counters' snapshots
        self.fault = fault
        # records
        self.phase = "setup"               # setup | settle | open | closed
        self.calls: List[tuple] = []       # (kind, t0, t1, t_dev, slots, ctx)
        self.payload_bits: List[tuple] = []  # (t0, bits, payloads)
        self.deliveries: List[tuple] = []  # (rid, now, wall, n, finished)
        self.admissions: List[tuple] = []  # (wall, prompt_len)
        self.by_seed: Dict[int, object] = {}
        self.t_open = self.t_close = None
        self.link_open = self.link_close = None
        self.now_open = self.now_close = None
        self.last_now = 0.0
        self.compiles_open = 0
        self.compiles_window = 0
        self.trace_span = None             # (t_start, t_stop) wall
        self.n_full_deliveries = 0
        self.audit: List[dict] = []
        self.served_rows: List[tuple] = []   # (kind, rid, (row, x, pos))
        self.n_audit = cell["audit"]["rounds"]
        self.n_drafts_seen = 0
        self.wanted: Dict[int, dict] = {}
        self.withdrawn = set()
        self._last_draft = None
        self._last_verify = None
        self.programs: Dict[str, list] = {}  # module: [jit, abstract args]
        self._install_hooks()

    # ------------------------------------------------------------------
    def _install_hooks(self):
        eng = self.engine
        edge_jit, cloud_jit = eng.edge._draft_jit, eng.cloud._verify_jit

        def draft_jit(dp, cache, x_in, pos_in, beta_in, keys):
            args = (dp, cache, x_in, pos_in, beta_in, keys)
            self._keep_program("jit__draft_round", edge_jit, args)
            cache, ys = edge_jit(*args)
            self._last_draft = (ys, x_in, pos_in, beta_in)
            return cache, ys

        def verify_jit(tp, cache, tokens_in, pos, q_hat, live, key):
            args = (tp, cache, tokens_in, pos, q_hat, live, key)
            self._keep_program("jit__verify_round", cloud_jit, args)
            out = cloud_jit(*args)
            if self.fault is not None:      # tests only: a broken step
                out = self.fault(cache, out)
            self._last_verify = ((tokens_in, pos, q_hat, live, key), out)
            return out

        eng.edge._draft_jit = draft_jit
        eng.cloud._verify_jit = verify_jit
        draft_slots, spec_slot = eng.draft_slots, eng.draft_speculative_slot
        verify_slots, admit_slot = eng.verify_slots, eng.admit_slot
        ann = (jax.profiler.TraceAnnotation if self.trace_dir
               else lambda name: contextlib.nullcontext())

        def ctx_of(slots):
            out = []
            for s in slots:
                r = self.slot_req.get(s)
                out.append(0 if r is None
                           else int(r.prompt.shape[0]) - 1 + len(r.tokens))
            return tuple(out)

        def w_draft(slots):
            self.tick()
            ctx = ctx_of(slots)
            t0 = time.perf_counter()
            with ann("bench.draft"):
                recs = draft_slots(slots)
            t1 = time.perf_counter()
            self.calls.append(("draft", t0, t1,
                               next(iter(recs.values())).t_slm,
                               tuple(slots), ctx))
            if self.phase == "open":
                ys, x_in, pos_in, _ = self._last_draft
                for s in slots:
                    self._keep_row("draft", s, _draft_row0(ys["q"], x_in,
                                                           pos_in, s))
                self._sample_draft(list(slots)[0])
            return recs

        def w_spec(slot, rec):
            self.tick()
            ctx = ctx_of([slot])
            t0 = time.perf_counter()
            with ann("bench.spec_draft"):
                spec = spec_slot(slot, rec)
            t1 = time.perf_counter()
            if spec is not None:
                self.calls.append(("spec", t0, t1, spec.round.t_slm,
                                   (slot,), ctx))
            return spec

        def w_verify(packed):
            self.tick()
            ctx = ctx_of(sorted(packed))
            t0 = time.perf_counter()
            with ann("bench.verify"):
                vb = verify_slots(packed)
            t1 = time.perf_counter()
            self.calls.append(("verify", t0, t1, vb.t_llm,
                               tuple(sorted(packed)), ctx))
            self.payload_bits.append(
                (t0, sum(len(b) for b in packed.values()) * 8, len(packed)))
            if self.phase == "open":
                (tokens_in, pos, _, _, _), (_, p, _, _) = self._last_verify
                for s in sorted(packed):
                    self._keep_row("verify", s,
                                   _verify_row0(tokens_in, pos, p, s))
            for s in sorted(packed):
                if s in self.wanted:
                    self._capture_verify(self.wanted.pop(s), s)
            return vb

        def w_admit(slot, prompt, seed, wire_codec=None):
            self.tick()
            t0 = time.perf_counter()
            with ann("bench.admit"):
                admit_slot(slot, prompt, seed, wire_codec=wire_codec)
            self.admissions.append((t0, int(np.asarray(prompt).shape[0])))
            self.slot_req[slot] = self.by_seed.get(seed)
            self.wanted.pop(slot, None)

        self.slot_req: Dict[int, object] = {}
        eng.draft_slots = w_draft
        eng.draft_speculative_slot = w_spec
        eng.verify_slots = w_verify
        eng.admit_slot = w_admit

    # -- the step programs' compiled text (traced runs) ------------------
    def _keep_program(self, module: str, jitted, args):
        if self.trace_dir and module not in self.programs:
            self.programs[module] = [jitted, jax.tree.map(_abstract, args)]

    def program_texts(self) -> Dict[str, str]:
        """Compiled HLO text of each step program, as it ran: lowered
        again from the shapes of its first call, which finds the
        executable already compiled in this process."""
        return {m: f.lower(*args).compile().as_text()
                for m, (f, args) in self.programs.items()}

    # -- audit ----------------------------------------------------------
    def _keep_row(self, kind: str, slot: int, arrays):
        req = self.slot_req.get(slot)
        if req is None:
            return
        for a in arrays:
            a.copy_to_host_async()
        self.served_rows.append((kind, req.rid, arrays))
        # rows a few calls old have reached the host: let the device
        # buffers go
        j = len(self.served_rows) - 8
        if j >= 0:
            k, rid, old = self.served_rows[j]
            self.served_rows[j] = (k, rid, tuple(np.asarray(a) for a in old))

    def _sample_draft(self, slot: int):
        """Reservoir sample (size n_audit) over the window's real draft
        calls, drawn from the run's seed."""
        self.n_drafts_seen += 1
        if len(self.audit) < self.n_audit:
            j = len(self.audit)
            self.audit.append(None)
        else:
            j = int(self.rng.integers(0, self.n_drafts_seen))
            if j >= self.n_audit:
                return
            old = self.audit[j]
            if self.wanted.get(old["slot"]) is old:
                del self.wanted[old["slot"]]
        ys, x_in, pos_in, beta_in = self._last_draft
        rec = {"slot": slot, "req": self.slot_req.get(slot),
               "draft": _draft_rows(ys["q"], ys["q_hat"], ys["token"],
                                    ys["beta"], ys["K"], x_in, pos_in,
                                    beta_in, slot),
               "verify": None}
        self.audit[j] = rec
        self.wanted[slot] = rec

    def _capture_verify(self, rec, slot: int):
        (tokens_in, pos, q_hat, live, key), (res, p, _, _) = \
            self._last_verify
        rec["verify"] = _verify_rows(tokens_in, pos, q_hat, live, key, p,
                                     res.n_accept, res.new_token, slot)

    # -- the window -----------------------------------------------------
    def link_counters(self):
        cells = self.session.topo.cells
        return (sum(c.uplink.bits_total for c in cells),
                sum(c.uplink.busy_total_s for c in cells))

    def tick(self):
        now = time.perf_counter()
        if self.phase == "settle" and self.engine.active.all() \
                and self.n_full_deliveries >= self.settle_deliveries:
            self.phase = "open"
            self.t_open = now
            self.now_open = self.last_now
            self.link_open = self.link_counters()
            self.queue_open = len(self.session.topo.waiting)
            self.compiles_open = self.clock.n
            if self.obs is not None:
                self.obs_open = self.obs.metrics.snapshot()
            if self.trace_dir:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # no per-function events
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                self.trace_span = [time.perf_counter(), None]
        elif self.phase == "open" and now - self.t_open >= self.seconds:
            self._close(now)

    def _close(self, now):
        self.phase = "closed"
        self.t_close = now
        self.now_close = self.last_now
        self.link_close = self.link_counters()
        self.queue_close = len(self.session.topo.waiting)
        self.compiles_window = self.clock.n - self.compiles_open
        if self.obs is not None:
            self.obs_close = self.obs.metrics.snapshot()
        if self.trace_span:
            self.trace_span[1] = now
            jax.profiler.stop_trace()
        topo = self.session.topo
        active = {id(r) for r in topo.active_requests}
        seen = active | {id(r) for r in (topo.waiting + topo.finished
                                         + topo.rejected)}
        for r in self.trace:
            if id(r) not in seen:
                r.max_new_tokens = 10**9      # withdrawn: rejected on arrival
                self.withdrawn.add(r.rid)
            elif id(r) in active:
                r.max_new_tokens = len(r.tokens) + 1   # no draft-ahead

    def deliver(self, req, n: int, now: float, finished: bool):
        wall = time.perf_counter()
        self.last_now = max(self.last_now, now)
        self.deliveries.append((req.rid, now, wall, n, finished))
        if self.phase == "settle" and self.engine.active.all():
            self.n_full_deliveries += 1

    # -- set-up ---------------------------------------------------------
    def warm(self):
        """Compile every program and eager path the window will use:
        prefill and slot write for each prompt bucket, draft, speculative
        draft, verify, verdict application, the audit slicers."""
        eng, rng = self.engine, np.random.default_rng(0)
        for S in sorted(self.traffic["prompt_buckets"]):
            eng.admit_slot(0, rng.integers(0, self.target.cfg.vocab, S,
                                           dtype=np.int32), seed=1)
            eng.release_slot(0)
        S = min(self.traffic["prompt_buckets"])
        for slot in (0, self.B - 1):
            eng.admit_slot(slot, rng.integers(0, self.target.cfg.vocab, S,
                                              dtype=np.int32), seed=2)
            rec = eng.draft_slots([slot])[slot]
            ys, x_in, pos_in, beta_in = self._last_draft
            jax.block_until_ready(_draft_rows(
                ys["q"], ys["q_hat"], ys["token"], ys["beta"], ys["K"],
                x_in, pos_in, beta_in, slot))
            jax.block_until_ready(_draft_row0(ys["q"], x_in, pos_in, slot))
            spec = eng.draft_speculative_slot(slot, rec)
            vb = eng.verify_slots({slot: rec.packed})
            (tokens_in, pos, q_hat, live, key), (res, p, _, _) = \
                self._last_verify
            jax.block_until_ready(_verify_rows(
                tokens_in, pos, q_hat, live, key, p, res.n_accept,
                res.new_token, slot))
            jax.block_until_ready(_verify_row0(tokens_in, pos, p, slot))
            v = eng.unpack_verdict_slot(slot,
                                        eng.pack_verdict_slot(
                                            slot, vb.verdicts[slot]))
            hit = spec is not None and eng.spec_premise_holds(spec, rec, v)
            eng.apply_verdict_slot(slot, v, rec, shrink=not hit)
            if spec is not None:
                eng.commit_speculative(spec)
            eng.release_slot(slot)
        jax.block_until_ready(eng.dcache)
        self.calls.clear()
        self.payload_bits.clear()
        self.admissions.clear()
        self.slot_req.clear()

    # -- run ------------------------------------------------------------
    def run(self, arrivals: List[traffic_mod.Arrival]):
        from harness.request import BenchRequest
        self.trace = [BenchRequest.make(a, self) for a in arrivals]
        self.by_seed = {r.seed: r for r in self.trace}
        self.settle_deliveries = SETTLE_ROUNDS * self.B
        self.phase = "settle"
        self.report = self.session.run_trace(self.trace)
        if self.phase != "closed":
            raise RuntimeError(
                f"the trace ran out before the window closed (phase "
                f"{self.phase}); the traffic file must offer more requests")

