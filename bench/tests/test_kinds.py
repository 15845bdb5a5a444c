"""Model kinds (``harness.kinds``): the dense kind draws the weights and
gives the reference logits that the harness drew and gave before kinds
existed (values recorded from that code, at a tiny size); a kind is a
file found by name, so a new one needs no harness edit; a configuration
that names no kind, or one that does not exist, fails at load and says
where kinds live; a ``"rule": "model"`` draft is built as its own kind."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT, TINY
from harness import cell, kinds

# Per tensor: float64 sum and sum of squares of the bf16 weights that
# seed 2**31 - 2 drew for TINY (target) and its draft_variant(2) before
# the weights moved into bench/kinds/dense.py.
WANT_W = {
    "tiny": {
        "bk": [0.157444, 1.357933], "bq": [-2.415585, 2.228716],
        "bv": [-1.294556, 1.514681], "embed": [13.241875, 511.978029],
        "final_norm": [130.0, 133.124969], "norm1": [254.101562, 254.527863],
        "norm2": [255.769531, 258.189438], "w_down": [12.132652, 257.39316],
        "w_gate": [-31.890883, 513.317685], "w_up": [-9.842544, 514.856449],
        "wk": [4.921207, 128.072609], "wo": [-14.962042, 256.429139],
        "wq": [-22.057928, 255.048479], "wv": [-6.558324, 127.719199]},
    "tiny-draft2x": {
        "bk": [-0.554699, 0.829883], "bq": [-0.540443, 0.644735],
        "bv": [-1.470676, 0.619671], "embed": [13.241875, 511.978029],
        "final_norm": [130.0, 133.124969], "norm1": [127.316406, 127.703171],
        "norm2": [127.894531, 128.962326], "w_down": [11.436085, 128.911146],
        "w_gate": [-12.368749, 128.527384], "w_up": [-10.025237, 128.49196],
        "wk": [10.335001, 63.43248], "wo": [-2.189058, 129.21343],
        "wq": [-11.785045, 63.683678], "wv": [2.638931, 63.721586]}}
# Reference logits of tokens arange(40) * 7 % V: the first three logits
# at rows 0, 17, 39, each row's sum, and the int8 control's at row 39.
WANT_Z = {
    "tiny": ([0.602627, -1.304828, 1.92734, 0.304856, -0.409472, -0.181454,
              0.116897, -0.864581, 0.331532], [-42.27538, -34.94603,
                                               -35.44671],
             [0.121507, -0.8865, 0.37435]),
    "tiny-draft2x": ([1.547532, 1.316898, 0.725984, 0.085161, -0.139,
                      -1.315819, 1.371954, 0.911109, 0.979113],
                     [2.86959, 11.06497, -7.74555],
                     [1.350166, 0.914882, 0.936092])}


def _conf(**kw):
    return dict({"name": "tiny", "kind": "dense", "model": dict(TINY),
                 "draft": {"rule": "draft_variant", "scale": 2}}, **kw)


def test_dense_kind_reproduces_the_weights_and_logits():
    target, draft = kinds.build(_conf(), 2**31 - 2, 2**31 - 2)
    assert target.kind is draft.kind is kinds.load("dense")
    for m in (target, draft):
        got = {k: [float(np.asarray(v, np.float64).sum()),
                   float((np.asarray(v, np.float64) ** 2).sum())]
               for k, v in m.w.items()}
        assert sorted(got) == sorted(WANT_W[m.cfg.name])
        for k, want in WANT_W[m.cfg.name].items():
            np.testing.assert_allclose(got[k], want, rtol=1e-6, atol=2e-6,
                                       err_msg=k)
        toks = np.arange(40) * 7 % m.cfg.vocab
        z = m.kind.logits(m.cfg, m.w, toks, [0, 17, 39])
        z8 = m.kind.logits(m.cfg, m.w, toks, [39], quant="int8")
        first, sums, ctl = WANT_Z[m.cfg.name]
        np.testing.assert_allclose(z[:, :3].ravel(), first, atol=2e-5)
        np.testing.assert_allclose(z.sum(-1), sums, atol=2e-4)
        np.testing.assert_allclose(z8[0, :3], ctl, atol=2e-5)
        assert m.kind.head(m.cfg, m.w).shape == (m.cfg.vocab,
                                                 m.cfg.d_model)


def test_a_kind_is_loaded_once():
    assert kinds.load("dense") is kinds.load("dense")
    assert set(kinds.API) <= set(dir(kinds.load("dense")))


@pytest.mark.parametrize("conf", [
    {"name": "x", "model": {}, "draft": {"rule": "draft_variant"}},
    {"name": "x", "kind": "nope", "model": {},
     "draft": {"rule": "draft_variant"}},
    {"name": "x", "kind": "../harness/cell", "model": {},
     "draft": {"rule": "draft_variant"}},
    {"name": "x", "kind": "dense", "model": {},
     "draft": {"rule": "model", "model": {}}},
    {"name": "x", "kind": "dense", "model": {},
     "draft": {"rule": "model", "kind": "nope", "model": {}}},
], ids=["missing", "unknown", "a-path", "draft-missing", "draft-unknown"])
def test_a_missing_or_unknown_kind_fails_naming_the_place(conf):
    with pytest.raises(ValueError, match="bench/kinds/"):
        kinds.of_config(conf)


def test_an_unknown_draft_rule_fails():
    with pytest.raises(ValueError, match="draft_variant or model"):
        kinds.of_config(_conf(draft={"rule": "half"}))


def test_a_cell_without_a_kind_fails_at_load(tmp_path):
    """cell.load reads the configuration and resolves its kinds before
    anything else runs."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf_path = os.path.join(ROOT, bench["configs"][0]["file"])
    with open(conf_path) as f:
        conf = json.load(f)
    del conf["kind"]
    (tmp_path / "bad.json").write_text(json.dumps(conf))
    bench["configs"][0]["file"] = "bad.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    name = next(w["name"] for w in bench["workloads"]
                if w["config"] == bench["configs"][0]["name"])
    with pytest.raises(ValueError, match="names no model kind.*bench/kinds/"):
        cell.load(str(tmp_path), name)


# -- a kind added by a file, in a copy of bench/ ------------------------------
TOY = '''"""A toy kind: the dense architecture with every weight scaled by a
half, to show a kind of its own is found by name."""
import jax

from harness import kinds

_dense = kinds.load("dense")
shapes, to_program, logits, head = (_dense.shapes, _dense.to_program,
                                    _dense.logits, _dense.head)
weight_bytes, kv_bytes_per_token = (_dense.weight_bytes,
                                    _dense.kv_bytes_per_token)
verify_call, draft_call, prefill_call = (_dense.verify_call,
                                         _dense.draft_call,
                                         _dense.prefill_call)


def make(cfg, seed):
    return jax.tree.map(lambda a: a * 0.5, _dense.make(cfg, seed))
'''

SCRIPT = '''
import json, sys
import numpy as np
from harness import kinds
conf = json.loads(sys.argv[1])
t, d = kinds.build(conf, 5, 6)
dense = kinds.load("dense")
full_t = dense.make(t.cfg, 5)
print(json.dumps({
    "kinds": [t.kind.__name__, d.kind.__name__],
    "names": [t.cfg.name, d.cfg.name],
    "halved": [bool(np.array_equal(np.asarray(t.w["wq"], np.float32),
                                   np.asarray(full_t["wq"], np.float32) * 0.5)),
               bool(t.kind is not dense)],
    "vocab": [t.cfg.vocab, d.cfg.vocab],
    "program": sorted(t.kind.to_program(t.cfg, t.w))}))
'''


@pytest.fixture(scope="module")
def bench_copy(tmp_path_factory):
    """A copy of bench/ with one file added: kinds/toy.py."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "bench" / "kinds" / "toy.py").write_text(TOY)
    return root / "bench"


def _build_in(copy, conf):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(copy),
                                           os.path.join(ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", SCRIPT, json.dumps(conf)],
                         cwd=copy, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_a_new_kind_is_found_by_name_without_a_harness_edit(bench_copy):
    for sub in ("harness", "metrics"):
        for f in os.listdir(os.path.join(BENCH, sub)):
            if f.endswith(".py"):
                with open(os.path.join(BENCH, sub, f), "rb") as a, \
                        open(bench_copy / sub / f, "rb") as b:
                    assert a.read() == b.read(), f
    got = _build_in(bench_copy, _conf(kind="toy"))
    # draft_variant inherits the target's kind
    assert got["kinds"] == ["bench_kind_toy", "bench_kind_toy"]
    assert got["halved"] == [True, True]
    assert got["program"] == ["body", "embed", "final_norm"]


def test_a_model_draft_is_built_as_its_own_kind(bench_copy):
    draft = dict(TINY, name="tiny-dense-draft", n_layers=1, d_model=64,
                 n_heads=2, n_kv_heads=1, d_ff=128)
    got = _build_in(bench_copy, _conf(
        kind="toy", draft={"rule": "model", "kind": "dense",
                           "model": draft}))
    assert got["kinds"] == ["bench_kind_toy", "bench_kind_dense"]
    assert got["names"] == ["tiny", "tiny-dense-draft"]
    assert got["vocab"] == [TINY["vocab"]] * 2


def test_a_model_draft_must_share_the_vocabulary():
    draft = dict(TINY, name="other-vocab", vocab=256)
    with pytest.raises(ValueError, match="vocabulary"):
        kinds.build(_conf(draft={"rule": "model", "kind": "dense",
                                 "model": draft}), 1, 2)
