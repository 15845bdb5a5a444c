#!/usr/bin/env python3
"""Bring-up check: serve full-width Qwen2.5-3B on one TPU chip.

    python3 chip_smoke.py

One process drives the normal serving path on the chip, phase by phase:

  build      the qwen2.5-3b target (36 layers, d_model 2048, GQA 16/2,
             vocab 151,936, tied embeddings) and its --draft-scale 2
             draft, random from --seed, through the builders that
             ``python -m repro.launch.serve`` uses;
  lockstep   a seeded 8-request Poisson trace through ServeSession with
             the observability gate on ([PASS-OBS]: round-phase spans,
             Theorem-1 rejection telemetry reconciled on chip numbers);
  pipelined  the same trace through the event-driven schedule — every
             request must finish with its token count, and the token
             streams must equal the lockstep leg's bit for bit;
  kernels    the compiled Pallas kernels (fused C-/K-SQS, top-K
             threshold, dense and paged flash-decode GQA) at the same
             widths, against their references;
  tcp        ``serve --transport tcp --cloud-port 0`` at --smoke width:
             the socket path and the threaded in-process server on the
             TPU backend, streams equal to the simulator
             ([PASS-TRANSPORT]).

Each phase prints its wall and compile seconds and the device's peak
bytes.  Nothing is caught: any failure exits nonzero.  With no TPU it
exits nonzero at once, naming the platform JAX found.  The last stdout
line is {"ok": true, "device": {"platform", "kind", "count"}}.
"""
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
ARCH = "qwen2.5-3b"
# 8 requests, 64-token prompts, 16-32 new tokens, 4 slots, C-SQS,
# L_max 8, entropy-coded wire
TRACE = ["--trace", "--method", "csqs", "--L-max", "8", "--wire-codec", "v2",
         "--n-requests", "8", "--rate", "4", "--prompt-len", "64",
         "--min-new-tokens", "16", "--max-new-tokens", "32",
         "--max-batch", "4", "--seed", "0"]


def require_tpu():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # not under /tmp
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{devs[0].platform!r} ({devs[0].device_kind}) and this "
                 f"check never falls back to it")
    return devs


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading a
    compiled program from the persistent cache), summed from its
    monitoring events."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration


def phase(name, clock, dev, fn):
    c0, t0 = clock.total, time.perf_counter()
    out = fn()
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"[phase {name}] wall {time.perf_counter() - t0:.1f}s  "
          f"compile {clock.total - c0:.1f}s  "
          f"peak_bytes_in_use {peak} ({peak / 2**30:.2f} GiB)", flush=True)
    return out


def modeled_span_ms(obs, name):
    """Durations (ms) of the modeled-clock spans called ``name`` — on
    that clock a draft span lasts the measured t_slm, a verify span
    the measured t_llm."""
    events = obs.tracer.chrome_trace()["traceEvents"]
    modeled = {e["pid"] for e in events
               if e.get("ph") == "M" and e.get("name") == "process_name"
               and e["args"]["name"].startswith("modeled")}
    return [e["dur"] / 1e3 for e in events
            if e.get("ph") == "X" and e["pid"] in modeled
            and e["name"] == name]


def serve_leg(serve, eng, tc, pipeline):
    from repro.serve import ServeSession
    args = serve.build_parser().parse_args(
        ["--arch", ARCH] + TRACE + [
            "--pipeline", pipeline,
            "--trace-out", os.path.join(OUT, f"{pipeline}_trace.json"),
            "--metrics-out", os.path.join(OUT, f"{pipeline}_metrics.json")])
    obs = serve.build_obs(args)
    rep = ServeSession(eng, serve.serve_config(args), obs=obs).run_trace(
        serve.make_trace(args, tc.vocab))
    serve.finish_obs(args, obs, tcp=False)
    slm, llm = modeled_span_ms(obs, "draft"), modeled_span_ms(obs, "verify")
    print(f"  {pipeline}: {rep.n_finished}/{rep.n_requests} finished, "
          f"{rep.total_tokens} tokens in {rep.n_rounds} verify batches; "
          f"median t_slm {statistics.median(slm):.2f} ms "
          f"({len(slm)} drafts), median t_llm "
          f"{statistics.median(llm):.2f} ms ({len(llm)} verifies)")
    bad = [r.rid for r in rep.requests if len(r.tokens) != r.max_new_tokens]
    assert rep.n_finished == rep.n_requests and not bad, \
        f"{pipeline}: unfinished or short requests {bad}"
    return {r.rid: tuple(r.tokens) for r in rep.requests}


def check_kernels(seed):
    """Compiled kernels vs references at Qwen2.5-3B widths.  Mosaic and
    XLA round exp, sums and f32 matmuls differently, so lattice counts
    may move by one unit and attention outputs by bf16-level error."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    from repro.kernels import sqs_fused as k

    B, V, NQ, NKV, HD, ELL = 4, 151936, 16, 2, 128, 100
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    logits = jax.random.normal(keys[0], (B, V), jnp.float32) * 3.0
    beta = jnp.full((B,), 2e-3, jnp.float32)

    def compare_sqs(name, rk, rr):
        qk, qr = np.asarray(rk.q_hat), np.asarray(rr.q_hat)
        n_mask = int((np.asarray(rk.mask) != np.asarray(rr.mask)).sum())
        err = float(np.abs(qk - qr).max())
        sums = np.round(qk * ELL).sum(-1)
        print(f"  {name}: K {np.asarray(rk.K).tolist()}  mask diffs "
              f"{n_mask}  max |dq_hat| {err:.3g}  lattice sums "
              f"{sums.tolist()}")
        assert n_mask == 0 and err <= 1.0 / ELL + 1e-6, name
        assert np.all(sums == ELL), name

    compare_sqs("sqs_threshold",
                ops.sqs_threshold(logits, beta, ell=ELL),
                ops.sqs_threshold(logits, beta, ell=ELL, use_ref=True))
    rk = ops.sqs_topk(logits, 64, ell=ELL)
    compare_sqs("sqs_topk", rk, ops.sqs_topk(logits, 64, ell=ELL,
                                             use_ref=True))
    assert np.all(np.asarray(rk.K) == 64)

    q = jax.nn.softmax(logits, axis=-1)
    tau = np.asarray(k.topk_threshold_call(q, 64, interpret=False))
    kth = np.asarray(ref.kth_largest_ref(q, 64))
    print(f"  topk_threshold: K-th largest inside [lo, hi] for "
          f"{int(((tau[:, 0] <= kth) & (kth <= tau[:, 1])).sum())}/{B} rows")
    assert np.all((tau[:, 0] <= kth) & (kth <= tau[:, 1]))

    def compare_attn(name, out, want):
        err = float(np.abs(np.asarray(out) - np.asarray(want)).max())
        print(f"  {name}: max |out - ref| {err:.3g}")
        assert err < 2e-2, name

    S = 2048
    qv = jax.random.normal(keys[1], (B, NQ, HD), jnp.bfloat16)
    kc = jax.random.normal(keys[2], (B, S, NKV, HD), jnp.bfloat16)
    vc = jax.random.normal(keys[3], (B, S, NKV, HD), jnp.bfloat16)
    pos = jax.random.randint(keys[4], (B,), S // 4, S, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = ops.gqa_decode(qv, kc, vc, pos, use_ref=True)
    compare_attn("gqa_decode", ops.gqa_decode(qv, kc, vc, pos), want)

    PS, MAXP = 64, 32
    n_pages = B * MAXP
    pool_k = jax.random.normal(keys[5], (n_pages + 1, PS, NKV, HD),
                               jnp.bfloat16)
    pool_v = jax.random.normal(keys[6], (n_pages + 1, PS, NKV, HD),
                               jnp.bfloat16)
    table = jax.random.permutation(keys[7], n_pages).reshape(B, MAXP)
    table = table.astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = ops.paged_gqa_decode(qv, pool_k, pool_v, table, pos,
                                    use_ref=True)
    compare_attn("paged_gqa_decode",
                 ops.paged_gqa_decode(qv, pool_k, pool_v, table, pos), want)


def main():
    devs = require_tpu()
    dev = devs[0]
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import param_count

    cache = enable_compile_cache()
    os.makedirs(OUT, exist_ok=True)
    clock = CompileClock()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache {cache}", flush=True)

    def build():
        args = serve.build_parser().parse_args(["--arch", ARCH] + TRACE)
        tc, dc, tp, dp = serve.build_models(args)
        for cfg, p in ((tc, tp), (dc, dp)):
            print(f"  {cfg.name}: {cfg.n_layers} layers, d_model "
                  f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, "
                  f"vocab {cfg.vocab}, {param_count(p)} params in "
                  f"{cfg.dtype}")
        eng = serve.build_engine(args, tc, dc, tp, dp, collect_theory=True)
        return tc, eng

    tc, eng = phase("build", clock, dev, build)
    lock = phase("lockstep", clock, dev,
                 lambda: serve_leg(serve, eng, tc, "lockstep"))
    pipe = phase("pipelined", clock, dev,
                 lambda: serve_leg(serve, eng, tc, "pipelined"))
    same = lock == pipe
    print(f"  lockstep vs pipelined: {len(lock)} streams, "
          f"{sum(map(len, lock.values()))} tokens, "
          f"{'bit-identical' if same else 'DIFFERENT'}", flush=True)
    assert same, "lockstep and pipelined token streams differ"
    del eng
    phase("kernels", clock, dev, lambda: check_kernels(seed=0))
    phase("tcp", clock, dev, lambda: serve.main(
        ["--arch", ARCH, "--smoke"] + TRACE
        + ["--transport", "tcp", "--cloud-port", "0"]))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
