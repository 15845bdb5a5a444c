"""Model kinds: what the harness knows of a model's architecture.

A configuration file (bench/configs/<name>.json) names its kind beside
``"model"``: ``"kind": "dense"``.  The kind is the file
``bench/kinds/<kind>.py``, loaded by path, so a new architecture is added
by adding a file.  It provides the functions of ``API``, and the harness
calls those and nothing else:

  shapes(cfg), make(cfg, seed)      semantic weights from the seed, on
                                    the device, in the served dtype
  to_program(cfg, w)                the program's parameter tree over the
                                    same arrays, checked against the
                                    program's own ``init_params``
  logits(cfg, w, tokens, rows, quant=None)
                                    the plain float32 reference at
                                    ``highest`` precision
  head(cfg, w)                      the (V, d) head, for check.HeadSpace
  weight_bytes(cfg), kv_bytes_per_token(cfg), verify_call(cfg, ctxs, L),
  draft_call(cfg, ctxs, L), prefill_call(cfg, S)
                                    operations and bytes by shapes
                                    (harness.counts)

The draft is given by the configuration's ``"draft"``:
``{"rule": "draft_variant", "scale": n}`` is the program's
``draft_variant`` of the target, of the target's kind;
``{"rule": "model", "kind": ..., "model": {...}}`` is a model of its own,
of any kind, with the target's vocabulary.
"""
from __future__ import annotations

import importlib.util
import os
import re
import sys
from typing import NamedTuple

DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "kinds")
API = ("shapes", "make", "to_program", "logits", "head", "weight_bytes",
       "kv_bytes_per_token", "verify_call", "draft_call", "prefill_call")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class Model(NamedTuple):
    cfg: object       # repro.configs.base.ModelConfig
    kind: object      # the kind's module
    w: dict           # semantic weights (kind.make)


def known() -> list:
    return sorted(f[:-3] for f in os.listdir(DIR) if f.endswith(".py"))


def load(name) -> object:
    """The kind module ``bench/kinds/<name>.py``, loaded once per process."""
    path = os.path.join(DIR, f"{name}.py")
    if not isinstance(name, str) or not _NAME.match(name) \
            or not os.path.isfile(path):
        raise ValueError(f"unknown model kind {name!r}: no file "
                         f"bench/kinds/{name}.py (known: {known()})")
    mod_name = "bench_kind_" + re.sub(r"\W", "_", name)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in API if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"bench/kinds/{name}.py lacks {missing} of the kind "
                         f"interface {list(API)}")
    # one module object per kind: its jitted functions compile once
    sys.modules[mod_name] = mod
    return mod


def of_config(conf: dict):
    """(target kind, draft kind) of a configuration file's contents."""
    if "kind" not in conf:
        raise ValueError(f"configuration {conf.get('name')!r} names no model "
                         f"kind: give \"kind\" beside \"model\", one of "
                         f"bench/kinds/ {known()}")
    target = load(conf["kind"])
    draft = conf["draft"]
    if draft["rule"] == "draft_variant":
        return target, target
    if draft["rule"] == "model":
        if "kind" not in draft:
            raise ValueError(f"configuration {conf.get('name')!r}: a "
                             f"\"model\" draft names its kind, one of "
                             f"bench/kinds/ {known()}")
        return target, load(draft["kind"])
    raise ValueError(f"configuration {conf.get('name')!r}: unknown draft "
                     f"rule {draft['rule']!r} (draft_variant or model)")


def build(conf: dict, seed_t: int, seed_d: int):
    """(target, draft) ``Model``s of a configuration file's contents, the
    weights drawn from the two seeds."""
    from repro import configs
    from repro.configs.base import ModelConfig
    tk, dk = of_config(conf)
    tc = ModelConfig(**conf["model"])
    d = conf["draft"]
    dc = configs.draft_variant(tc, d["scale"]) \
        if d["rule"] == "draft_variant" else ModelConfig(**d["model"])
    if dc.vocab != tc.vocab:
        raise ValueError(f"configuration {conf.get('name')!r}: the draft's "
                         f"vocabulary {dc.vocab} is not the target's "
                         f"{tc.vocab}")
    return Model(tc, tk, tk.make(tc, seed_t)), Model(dc, dk, dk.make(dc,
                                                                     seed_d))
