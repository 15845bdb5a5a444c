"""The comparison that decides ``correct``.

It compares what the timed path produced with the plain reference
(harness.reference), run over each request's own prompt and served
tokens, on two samples from the window (harness.session):

* every served position: the first row of each real draft step's q and
  each verify call's p, at the position of the request's last served
  token.  One reference pass per audited request and model covers all
  of that request's positions;
* the audited rounds: all L+1 positions of a draft step and of the
  verify call that carries its payload, for a reservoir of rounds drawn
  from the seed.

The numbers:

  target_gap, draft_gap
                  the widest, over every compared row, of the logit gap:
                  the RMS over the vocabulary of the program's logits
                  (recovered as T log p, which the softmax leaves exact
                  up to one shift per row) minus the reference's, after
                  the row's mean difference is taken out.  In logit
                  units; every token's logit counts, not only the few
                  the temperature leaves weight on;
  target_rest, draft_rest
                  the widest RMS of the part of that difference that no
                  final hidden state explains: what is left after its
                  least-squares fit by the head's columns (HeadSpace).
                  Rounding inside the model moves the hidden state and
                  leaves this part at the logits' own rounding; weights
                  stored at a lower precision put their error here;
  target_tv, draft_tv
                  the widest total-variation distance between the
                  program's distribution and softmax(reference / T)
                  (reported beside the gaps; compared only where the
                  cell's file gives a limit);
  positions       served positions compared (both models);
  lattice_dev     max |b - l * q~| over every audited position and
                  token, where b are the draft's lattice counts and q~
                  the support-renormalised q recomputed here
                  (Algorithm 2 bounds it below 1);
  sqs_faults      counts that break the SQS rules: a row whose counts do
                  not sum to l, a count outside the support rule (C-SQS
                  q >= beta or the argmax; K-SQS the top K);
  wire_faults     live positions whose lattice counts or token differ
                  between what the draft produced and what the verify
                  decoded (counts, not floats: the device's b / l and
                  the decoder's may differ in the last bit);
  state_faults    rounds or positions whose inputs do not continue the
                  request's served sequence (position, last token);
  emission_faults audited rounds whose accept count or new token differs
                  from the rejection sampler recomputed here from p,
                  q-hat and the round's uniforms, or from what was served.

The control (``quant``) puts the reference at a lower weight precision
in the program's place: its distributions, made in float32 from its
logits as the program makes them, are read by the same numbers and
judged by the same ``verdict``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference

ROW_BUCKET = 32     # rows are padded to a multiple: few programs
MIN_POSITIONS = 20  # served positions a run must compare, both models


def _softmax_t(logits, T):
    z = np.asarray(logits, np.float64) / max(T, 1e-4)
    z -= z.max(-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(-1, keepdims=True)


def _softmax_f32(logits, T):
    """The distribution as the program makes it from its logits."""
    z = np.asarray(logits, np.float32) / np.float32(max(T, 1e-4))
    z = z - z.max(-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(-1, keepdims=True)


@jax.jit
def _head_space(W):
    """Centred columns of the head (V, d) and the Cholesky factor of
    their Gram matrix."""
    Wc = W.astype(jnp.float32)
    Wc = Wc - Wc.mean(0)
    return Wc, jnp.linalg.cholesky(Wc.T @ Wc)


@jax.jit
def _rest_rms(Wc, chol, E):
    """RMS of each centred row of E (n, V) after its least-squares fit
    by the head's columns is taken out."""
    a = jax.scipy.linalg.cho_solve((chol, True), (E @ Wc).T).T
    R = E - a @ Wc.T
    return jnp.sqrt(jnp.mean(R * R, -1))


class HeadSpace:
    """The span of a model's head: every logit vector the model can make
    is W h for some final hidden state h (W the (V, d) head), so an
    error of the hidden state, however large, lies in this span.  What
    lies outside it comes from the head's own weights or the logits'
    rounding."""

    def __init__(self, w: dict):
        W = w["head"].T if "head" in w else w["embed"]
        with jax.default_matmul_precision("highest"):
            self.Wc, self.chol = _head_space(W)

    def rest(self, e: np.ndarray) -> np.ndarray:
        out = []
        with jax.default_matmul_precision("highest"):
            for i in range(0, len(e), ROW_BUCKET):
                blk = np.zeros((ROW_BUCKET, e.shape[1]), np.float32)
                n = len(e[i:i + ROW_BUCKET])
                blk[:n] = e[i:i + ROW_BUCKET]
                out.append(np.asarray(_rest_rms(self.Wc, self.chol,
                                                jnp.asarray(blk)))[:n])
        return np.concatenate(out).astype(np.float64)


def row_readings(prob, ref_logits, T, space: HeadSpace):
    """Per row of the distribution ``prob`` (rows, V) against the
    reference logits (rows, V): (logit gap, the gap's rest outside the
    head's span, total variation)."""
    p = np.asarray(prob, np.float64)
    z = np.asarray(ref_logits, np.float64)
    # log p is exact down to here in f32; a token below it (none has
    # been seen at T 0.2) counts as no error rather than a wrong one
    ok = p > 1e-30
    e = np.where(ok, T * np.log(np.where(ok, p, 1.0)) - z, 0.0)
    e -= np.where(ok, (e.sum(-1) / ok.sum(-1))[:, None], 0.0)
    gap = np.sqrt((e * e).mean(-1))
    tv = 0.5 * np.abs(p - _softmax_t(z, T)).sum(-1)
    return gap, space.rest(e), tv


def support_ref(q, beta, method: dict):
    """The SQS support rule on one f32 row."""
    if method["name"] == "csqs":
        mask = q >= np.float32(beta)
        mask[int(np.argmax(q))] = True
        return mask
    K = min(method["K"], q.shape[0])
    kth = np.sort(q)[::-1][K - 1]
    mask = q >= kth
    keep = np.cumsum(mask) <= K
    return mask & keep


@functools.partial(jax.jit, static_argnums=(5,))
def _resample(p, q_hat, tokens, live, key, L):
    """The rejection sampler, written out here from its definition:
    accept draft i while u_i < p_i(d_i) / q_i(d_i); at the first
    rejection sample from norm(max(p - q, 0)), else from the bonus p."""
    kk = jax.random.split(key)
    u = jax.random.uniform(kk[0], (L,), jnp.float32, 1e-12, 1.0)
    idx = jnp.arange(L)
    q_tok = q_hat[idx, tokens]
    p_tok = p[idx, tokens]
    ok = (u < jnp.minimum(1.0, p_tok / jnp.maximum(q_tok, 1e-30))) & live
    n_acc = jnp.cumprod(ok.astype(jnp.int32)).sum()
    rejected = n_acc < live.sum()
    p_T = p[n_acc]
    q_T = jnp.where(n_acc < L, q_hat[jnp.minimum(n_acc, L - 1)], 0.0)
    res = jnp.maximum(p_T - q_T, 0.0)
    rs = res.sum()
    res = jnp.where(rs > 1e-30, res / jnp.maximum(rs, 1e-30), p_T)
    dist = jnp.where(rejected, res, p_T)
    new = jax.random.categorical(kk[1], jnp.log(jnp.maximum(dist, 1e-30)))
    return n_acc, new


def audit_rounds(run) -> list:
    """Host copies of the audited rounds that have both halves."""
    out = []
    for rec in run.audit:
        if rec is None or rec["verify"] is None or rec["req"] is None:
            continue
        d = [np.asarray(x) for x in rec["draft"]]
        v = [np.asarray(x) for x in rec["verify"]]
        out.append({
            "rid": rec["req"].rid,
            "prompt": np.asarray(rec["req"].prompt),
            "served": np.asarray(rec["req"].tokens, np.int32),
            "q": d[0], "q_hat": d[1], "d_tok": d[2], "betas": d[3],
            "x_in": int(d[5]), "pos": int(d[6]), "beta_in": float(d[7]),
            "v_tokens": v[0], "v_pos": int(v[1]), "v_qhat": v[2],
            "live": v[3], "key": v[4], "p": v[5], "n_accept": int(v[6]),
            "new_token": int(v[7])})
    return out


def audit_positions(run) -> dict:
    """{rid: {"prompt", "served", "draft": [(pos, x, q row)], "verify":
    [(pos, x, p row)]}} for every served position the window kept."""
    reqs = {r.rid: r for r in run.trace}
    out = {}
    for kind, rid, arrays in run.served_rows:
        row, x, pos = (np.asarray(a) for a in arrays)
        r = reqs[rid]
        d = out.setdefault(rid, {
            "prompt": np.asarray(r.prompt),
            "served": np.asarray(r.tokens, np.int32),
            "draft": [], "verify": []})
        d[kind].append((int(pos), int(x), row))
    return out


def _ref_rows(cfg, w, seq, rows, quant=None):
    """Reference logits at ``rows`` of the teacher-forced ``seq``."""
    n = len(rows)
    padded = list(rows) + [rows[-1]] * (-n % ROW_BUCKET)
    return reference.logits(cfg, w, seq, padded, quant=quant)[:n]


def compare(rounds, positions, tc, dc, tw, dw, method: dict, engine: dict,
            quant=()) -> dict:
    """Numbers compared for the audited ``rounds`` (audit_rounds) and
    served ``positions`` (audit_positions).  For each lower weight
    precision in ``quant`` the control's readings of the model numbers
    are returned under ``control[mode]``."""
    T, L, ell = engine["temperature"], engine["L_max"], method["ell"]
    model_nums = [w + n for w in ("target_", "draft_")
                  for n in ("gap", "rest", "tv")]
    out = {n: 0.0 for n in model_nums}
    out.update({"lattice_dev": 0.0, "sqs_faults": 0, "wire_faults": 0,
                "state_faults": 0, "emission_faults": 0,
                "rounds": len(rounds), "positions": 0})
    ctl = {m: {n: 0.0 for n in model_nums} for m in quant}

    space = {"target": HeadSpace(tw), "draft": HeadSpace(dw)}

    def widest(into, who, readings):
        for name, r in zip(("_gap", "_rest", "_tv"), readings):
            into[who + name] = max(into[who + name], float(r.max()))

    def read(who, prob, ref, low):
        widest(out, who, row_readings(prob, ref, T, space[who]))
        for m, z in low.items():
            widest(ctl[m], who, row_readings(_softmax_f32(z, T), ref, T,
                                             space[who]))

    # -- every served position, one reference pass per request and model
    for rid in sorted(positions):
        d = positions[rid]
        seq = np.concatenate([d["prompt"], d["served"]]).astype(np.int32)
        for who, kind, cfg, w in (("draft", "draft", dc, dw),
                                  ("target", "verify", tc, tw)):
            keep = [(pos, row) for pos, x, row in d[kind]
                    if pos < len(seq) and seq[pos] == x]
            out["state_faults"] += len(d[kind]) - len(keep)
            if not keep:
                continue
            out["positions"] += len(keep)
            rows = [pos for pos, _ in keep]
            part = seq[:max(rows) + 1]
            read(who, np.stack([row for _, row in keep]),
                 _ref_rows(cfg, w, part, rows),
                 {m: _ref_rows(cfg, w, part, rows, m) for m in quant})

    # -- the audited rounds, all L+1 positions
    for r in rounds:
        seq = np.concatenate([r["prompt"], r["served"]]).astype(np.int32)
        pos = r["pos"]
        if not (pos < len(seq) and seq[pos] == r["x_in"]
                and r["v_pos"] == pos and r["v_tokens"][0] == r["x_in"]):
            out["state_faults"] += 1
            continue
        rows = np.arange(pos, pos + L + 1)
        # -- models against the reference --------------------------------
        for who, cfg, w, toks, prob in (
                ("draft", dc, dw, r["d_tok"][:L], r["q"]),
                ("target", tc, tw, r["v_tokens"][1:], r["p"])):
            full = np.concatenate([seq[:pos + 1], toks])
            read(who, prob, reference.logits(cfg, w, full, rows),
                 {m: reference.logits(cfg, w, full, rows, quant=m)
                  for m in quant})
        # -- SQS: support rule and lattice counts -------------------------
        for i in range(L + 1):
            q = r["q"][i]
            beta = r["beta_in"] if i == 0 else r["betas"][i - 1]
            mask = support_ref(q, beta, method)
            b = np.rint(r["q_hat"][i].astype(np.float64) * ell)
            qt = np.where(mask, q, 0).astype(np.float64)
            qt /= qt.sum()
            out["sqs_faults"] += int(b.sum() != ell) + int(
                np.count_nonzero((b > 0) & ~mask))
            out["lattice_dev"] = max(out["lattice_dev"],
                                     float(np.abs(b - ell * qt).max()))
        # -- the wire: what the verify decoded is what the draft made -----
        n = int(r["live"].sum())
        out["wire_faults"] += int(np.count_nonzero(
            r["v_tokens"][1:1 + n] != r["d_tok"][:n]))
        counts_v = np.rint(r["v_qhat"].astype(np.float64) * ell)
        counts_d = np.rint(r["q_hat"][:L].astype(np.float64) * ell)
        out["wire_faults"] += int(np.count_nonzero(
            (counts_v[:n] != counts_d[:n]).any(-1)))
        out["wire_faults"] += int(np.count_nonzero(counts_v[n:]))
        # -- the sampler and the delivery --------------------------------
        n_acc, new = _resample(jnp.asarray(r["p"]), jnp.asarray(r["v_qhat"]),
                               jnp.asarray(r["v_tokens"][1:]),
                               jnp.asarray(r["live"]), jnp.asarray(r["key"]),
                               L)
        n_acc, new = int(n_acc), int(new)
        want = list(r["v_tokens"][1:1 + n_acc]) + [new]
        j0 = pos - (len(r["prompt"]) - 1)
        got = list(r["served"][j0:j0 + n_acc + 1])
        if (n_acc != r["n_accept"] or new != r["new_token"]
                or got != want[:len(got)] or not got):
            out["emission_faults"] += 1
    if quant:
        out["control"] = ctl
    return out


def verdict(nums: dict, limits: dict, audit: dict):
    """[(name, value, limit, ok)] and whether every one holds.  The model
    numbers named in ``limits`` are compared against them."""
    rows = [("rounds", nums["rounds"], audit["min_rounds"],
             nums["rounds"] >= audit["min_rounds"]),
            ("positions", nums["positions"], MIN_POSITIONS,
             nums["positions"] >= MIN_POSITIONS)]
    for name in sorted(limits):
        rows.append((name, nums[name], limits[name],
                     nums[name] <= limits[name]))
    rows.append(("lattice_dev", nums["lattice_dev"], 1.0,
                 nums["lattice_dev"] < 1.0))
    for name in ("sqs_faults", "wire_faults", "state_faults",
                 "emission_faults"):
        rows.append((name, nums[name], 0, nums[name] == 0))
    return rows, all(ok for *_, ok in rows)
