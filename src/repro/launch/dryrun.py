import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run launcher (deliverable (e)).

For every (architecture × input shape × mesh) combination this lowers and
compiles the corresponding step function against ShapeDtypeStruct inputs —
no allocation — and records memory / cost / collective analysis:

    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all

Shapes → step functions:
    train_4k    → train_step (loss+grad+AdamW, donated state)
    prefill_32k → prefill (prompt → cache)
    decode_32k  → decode_step (ONE token against a seq_len KV cache)
    long_500k   → decode_step, sub-quadratic variants only (DESIGN.md)
"""
import argparse
import dataclasses
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.base import INPUT_SHAPES, for_shape, supports_shape
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh, mesh_axes
from repro.models import model as model_mod
from repro.sharding.partition import Partitioner
from repro.train import optimizer as opt_mod
from repro.train.trainer import make_train_step

ENC_LEN = 4096          # audio-frontend stub frames (enc-dec combos)


def _bf16(tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
        tree)


def _params_sds(cfg, serve: bool):
    sds = jax.eval_shape(functools.partial(model_mod.init_params, cfg),
                         jax.random.PRNGKey(0))
    return _bf16(sds) if serve else sds


def _zeros_spec_like(tree):
    return jax.tree.map(lambda _: P(), tree)


def build_lowered(cfg, shape, mesh, axes, fsdp: bool,
                  seq_shard_fallback: bool = None):
    if seq_shard_fallback is None:
        seq_shard_fallback = os.environ.get("REPRO_SEQ_SHARD_KV") == "1"
    part = Partitioner(cfg, mesh, axes, fsdp=fsdp,
                       seq_shard_fallback=seq_shard_fallback)
    if os.environ.get("REPRO_SHARD_ACTS") == "1":
        # sequence-parallel residuals are attention/FFN-only: SSM blocks
        # mix along the sequence, so sharding S over `model` between
        # layers forces full gathers inside every Mamba/xLSTM layer
        # (measured: jamba train 1.3 -> 3.5 TiB/chip).
        has_ssm = any(b in ("mamba", "mlstm", "slstm")
                      for b in cfg.block_pattern)
        model_mod.set_mesh(
            mesh, axes,
            seq_parallel=(os.environ.get("REPRO_SEQ_PARALLEL") == "1"
                          and not has_ssm))
    else:
        model_mod.set_mesh(None, None)
    from repro.models import moe as moe_mod
    if os.environ.get("REPRO_MOE_GROUPS") == "1" and not \
            (shape.kind == "train" and fsdp):
        # shard_map MoE assumes model-axis-only weight sharding; under
        # FSDP training the in_specs would force full weight re-gathers
        # (measured: jamba train 1.3 -> 3.4 TiB/chip) — fall back.
        moe_mod.GROUPS = mesh.shape[axes.data]
    else:
        moe_mod.GROUPS = 1
    kind = shape.kind
    B, S = shape.batch, shape.seq

    def ns(spec_tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    if kind == "train":
        params = _params_sds(cfg, serve=False)
        opt_state = jax.eval_shape(opt_mod.init_state, params)
        batch = {"tokens": jax.ShapeDtypeStruct((B, S + 1), jnp.int32)}
        if cfg.n_encoder_layers:
            batch["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, ENC_LEN, cfg.d_model), jnp.bfloat16)
        pspec = part.param_specs(params)
        ospec = part.opt_state_specs(params)
        bspec = part.batch_specs(batch)
        step = make_train_step(cfg, opt_mod.AdamWConfig(), microbatches=1)
        fn = jax.jit(step,
                     in_shardings=(ns(pspec), ns(ospec), ns(bspec)),
                     out_shardings=(ns(pspec), ns(ospec), None),
                     donate_argnums=(0, 1))
        return fn.lower(params, opt_state, batch)

    params = _params_sds(cfg, serve=True)
    pspec = part.param_specs(params)

    if kind == "prefill":
        tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
        args = {"tokens": tokens}
        if cfg.n_encoder_layers:
            args["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, ENC_LEN, cfg.d_model), jnp.bfloat16)
        aspec = part.batch_specs(args)

        def fn(params, args):
            return model_mod.prefill(cfg, params, args["tokens"],
                                     enc_embeds=args.get("enc_embeds"),
                                     cache_len=S)
        jf = jax.jit(fn, in_shardings=(ns(pspec), ns(aspec)))
        return jf.lower(params, args)

    # decode: ONE new token against a cache of seq_len
    shard_seq = shape.long_context       # batch=1 → context parallelism
    cache = jax.eval_shape(
        functools.partial(model_mod.init_cache, cfg, B, S,
                          enc_seq=ENC_LEN if cfg.n_encoder_layers else 0))
    cspec = part.cache_specs(cache, shard_seq=shard_seq)
    token = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    tspec = P(part._dp(B)) if B > 1 else P()

    def fn(params, token, cache, pos):
        return model_mod.decode_step(cfg, params, token, cache, pos)
    jf = jax.jit(fn, in_shardings=(ns(pspec), ns(tspec), ns(cspec),
                               ns(tspec)),
                 out_shardings=(None, ns(cspec)), donate_argnums=(2,))
    return jf.lower(params, token, cache, pos)


def _reduced_cfg(cfg, n_units: int):
    """Same arch with n_units body periods (and encoder layers) — used to
    linearise per-period HLO cost (roofline scan correction)."""
    return dataclasses.replace(
        cfg,
        n_layers=cfg.n_prefix_layers + n_units * cfg.period,
        n_encoder_layers=min(cfg.n_encoder_layers, n_units)
        if cfg.n_encoder_layers else 0)


def calibrate_combo(arch: str, shape_name: str, multi_pod: bool,
                    out_dir: str) -> dict:
    """Add 1p/2p scan-calibration costs to an existing dry-run record."""
    shape = INPUT_SHAPES[shape_name]
    cfg = for_shape(configs.get_config(arch), shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    path = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_name}.json")
    rec = json.load(open(path))
    if rec.get("status") != "ok":
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = mesh_axes(multi_pod=multi_pod)
    fsdp = bool(rec.get("fsdp"))
    cal = {"n_units": max(cfg.n_periods, cfg.n_encoder_layers, 1)}
    os.environ["REPRO_UNROLL_FOR_COST"] = "1"   # trip-1 inner scans
    try:
        for n_units in (0, 1):
            cfg_r = _reduced_cfg(cfg, n_units)
            with mesh:
                lowered = build_lowered(cfg_r, shape, mesh, axes, fsdp)
            ca = lowered.compile().cost_analysis() or {}
            cal[f"cost_{n_units}p"] = {
                k: ca[k] for k in ("flops", "bytes accessed") if k in ca}
        rec["scan_calibration"] = cal
        rec["calibration_status"] = "ok"
    except Exception as e:  # noqa: BLE001
        rec["calibration_status"] = f"error: {type(e).__name__}: {e}"
    finally:
        os.environ.pop("REPRO_UNROLL_FOR_COST", None)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def run_combo(arch: str, shape_name: str, multi_pod: bool,
              out_dir: str, fsdp=None) -> dict:
    shape = INPUT_SHAPES[shape_name]
    base = configs.get_config(arch)
    cfg = for_shape(base, shape)
    if os.environ.get("REPRO_KV_INT8") == "1" and shape.kind == "decode" \
            and not cfg.is_mla:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "batch": shape.batch, "seq": shape.seq,
           "attention": cfg.attention,
           "params_total": base.param_count(),
           "params_active": base.param_count(active_only=True)}
    def _dump(r):
        if out_dir:
            # preserve calibration results from a previous pass
            old_path = os.path.join(
                out_dir, f"{arch}_{shape_name}_{mesh_name}.json")
            if os.path.exists(old_path):
                try:
                    old = json.load(open(old_path))
                    for key in ("scan_calibration", "calibration_status"):
                        if key in old and key not in r:
                            r[key] = old[key]
                except Exception:
                    pass
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            fn = f"{arch}_{shape_name}_{mesh_name}.json"
            with open(os.path.join(out_dir, fn), "w") as f:
                json.dump(r, f, indent=1, default=str)
        return r

    ok, why = supports_shape(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return _dump(rec)
    if not (not shape.long_context or cfg.sub_quadratic):
        rec["status"] = "skipped"
        rec["reason"] = "full attention at 500k (DESIGN.md long_500k policy)"
        return _dump(rec)
    if fsdp is None:
        # FSDP when even fully-model-sharded AdamW state would blow HBM
        fsdp = shape.kind == "train" and base.param_count() > 50e9
    rec["fsdp"] = bool(fsdp)

    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = mesh_axes(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    try:
        t0 = time.time()
        with mesh:
            lowered = build_lowered(cfg, shape, mesh, axes, fsdp)
        rec["lower_s"] = round(time.time() - t0, 1)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 1)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "code_bytes": ma.generated_code_size_in_bytes,
            "peak_per_device": (ma.argument_size_in_bytes
                                + ma.output_size_in_bytes
                                + ma.temp_size_in_bytes
                                - ma.alias_size_in_bytes),
        }
        ca = compiled.cost_analysis() or {}
        rec["cost"] = {k: ca[k] for k in ("flops", "bytes accessed")
                       if k in ca}
        txt = compiled.as_text()
        rec["hlo_lines"] = len(txt.splitlines())
        rec["collectives"] = hlo_analysis.collective_summary(
            txt, scan_trip_count=max(cfg.n_periods, 1))
        rec["n_chips"] = int(n_chips)
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return _dump(rec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--calibrate", action="store_true",
                    help="add 1p/2p scan-correction costs to existing "
                         "records")
    args = ap.parse_args()

    archs = configs.ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multi_pod]

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                path = os.path.join(
                    args.out, f"{arch}_{shape}_{mesh_name}.json")
                if args.skip_existing and os.path.exists(path):
                    try:
                        old = json.load(open(path))
                        if old.get("status") in ("ok", "skipped"):
                            print(f"[skip] {arch} {shape} {mesh_name}")
                            continue
                    except Exception:
                        pass
                if args.calibrate:
                    try:
                        rec = calibrate_combo(arch, shape, mp, args.out)
                        print(f"[cal {arch} | {shape} | {mesh_name}] "
                              f"{rec.get('calibration_status', 'n/a')}",
                              flush=True)
                    except FileNotFoundError:
                        print(f"[cal {arch} | {shape} | {mesh_name}] "
                              f"missing record", flush=True)
                    continue
                rec = run_combo(arch, shape, mp, args.out)
                msg = rec["status"]
                if rec["status"] == "ok":
                    gb = rec["memory"]["peak_per_device"] / 2**30
                    msg += (f" peak={gb:.2f}GiB/chip "
                            f"lower={rec['lower_s']}s "
                            f"compile={rec['compile_s']}s "
                            f"coll={rec['collectives']['total_collective_bytes']/2**30:.2f}GiB")
                elif rec["status"] == "error":
                    msg += " " + rec["error"][:200]
                else:
                    msg += " " + rec.get("reason", "")
                print(f"[{arch} | {shape} | {mesh_name}] {msg}", flush=True)


if __name__ == "__main__":
    main()
