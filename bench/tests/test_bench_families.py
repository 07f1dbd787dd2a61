"""The model-family seam: ``families/dense.py`` gives bitwise what the
functions it binds give; a family module in another directory is found
by the name a configuration gives and used by the harness, with no file
of ``bench/`` edited; a configuration that names no family, or one with
no module, fails with the file looked for (CPU)."""

import functools
import re
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import check, spec, weights  # noqa: E402
from bench import run as runmod  # noqa: E402
from bench.reference import dense  # noqa: E402
from bench.work import model_step, paged_attention  # noqa: E402

HERE = Path(__file__).resolve().parent
CHIP = spec.BENCH_DIR / "configs"
CONFIGS = {"tiny_dense": HERE / "tiny_dense.json",
           "tiny_stablelm": HERE / "tiny_stablelm.json",
           "qwen1.5-0.5b": CHIP / "qwen1.5-0.5b.json",
           "stablelm-3b.l8": CHIP / "stablelm-3b.l8.json"}
SEED = 2**33 + 5
LENS = [1, 17, 300, 2048]


def config(name: str) -> dict:
    return spec.load_json(CONFIGS[name])


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_family_gives_the_dense_sizes_program_and_work(name):
    c = config(name)
    fam = spec.load_family(c)
    m = weights.Dims.from_config(c)
    assert fam.dims(c) == m
    cfg, got = runmod.program_config(c)
    assert got == m
    want = fam.program_want(m)
    assert want["family"] == "dense" and len(want) == 14
    assert all(getattr(cfg, k) == v for k, v in want.items())
    assert fam.decode_flops(m, LENS) == model_step.decode_flops(
        LENS, m.layers, m.d, m.ff, m.heads, m.kv_heads, m.head_dim, m.vocab)
    for kv in ("bf16", "int8"):
        assert fam.attention_work(m, LENS, kv) == paged_attention.work(
            LENS, m.heads, m.kv_heads, m.head_dim, kv)
    assert fam.attention_launches(m) == m.layers


@pytest.mark.parametrize("name", ["tiny_dense", "tiny_stablelm"])
def test_dense_family_weights_and_reference_are_bitwise_the_bound_ones(name):
    import jax
    c = config(name)
    fam = spec.load_family(c)
    m = fam.dims(c)
    got, want = fam.make_params(m, SEED), weights.make_params(m, SEED)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    rng = np.random.default_rng(1)
    seq = rng.integers(0, m.vocab, 40).astype(np.int32)
    rows = np.arange(8, 40, 4, dtype=np.int32)
    ids = rng.integers(0, m.vocab, len(rows)).astype(np.int32)
    for rounding in ("f32", "fp8"):
        h = fam.hidden(got, seq, m, rounding)
        h0 = dense.hidden(want, seq, m, rounding)
        assert np.array_equal(np.asarray(h), np.asarray(h0))
        for control in ({}, {"rounding": rounding}, {"stats": "bf16"}):
            a = fam.head_stats(got, h, rows, ids, m, **control)
            b = dense.head_stats(want, h0, rows, ids, m, **control)
            assert a.keys() == b.keys() == {"max", "argmax", "lse", "at"}
            assert all(np.array_equal(a[k], b[k]) for k in a)


# A family of another directory: the dense model under a name of its own,
# with sizes of a type of its own (a dataclass, which needs its module in
# sys.modules), that notes what the harness calls.
TOY = '''
from __future__ import annotations

from dataclasses import dataclass

from bench.families import dense

CALLS = set()


@dataclass(frozen=True)
class Dims:
    inner: object

    @property
    def layers(self):
        return self.inner.layers

    @property
    def vocab(self):
        return self.inner.vocab


def _noted(fn):
    def call(m, *a, **k):
        CALLS.add(fn.__name__)
        return fn(m.inner, *a, **k)
    return call


def dims(config):
    CALLS.add("dims")
    return Dims(dense.dims(config))


program_want = _noted(dense.program_want)
make_params = _noted(dense.make_params)
decode_flops = _noted(dense.decode_flops)
attention_work = _noted(dense.attention_work)
attention_launches = _noted(dense.attention_launches)


def hidden(params, seq, m, rounding="f32"):
    CALLS.add("hidden")
    return dense.hidden(params, seq, m.inner, rounding)


def head_stats(params, h, rows, ids, m, **control):
    CALLS.add("head_stats")
    return dense.head_stats(params, h, rows, ids, m.inner, **control)
'''


def test_a_family_in_another_directory_is_found_and_used(tmp_path,
                                                         monkeypatch):
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 256, 20).tolist()
    reqs = [check.Served(rid=0, prompt=prompt,
                         output=rng.integers(0, 256, 6).tolist(),
                         logprobs=[-1.0] * 6)]
    controls = tuple(check.CONTROLS)
    base = config("tiny_dense")
    want = check.compare(base, SEED, reqs, 26, controls=controls)

    families = tmp_path / "families"
    families.mkdir()
    (families / "toy.py").write_text(TOY)
    monkeypatch.setattr(spec, "load_family", functools.partial(
        spec.load_family, families_dir=families))
    c = config("tiny_dense")
    c["architecture"]["family"] = "toy"
    fam = spec.load_family(c)
    assert Path(fam.__file__) == families / "toy.py"
    assert spec.load_family(c) is fam
    cfg, m = runmod.program_config(c)
    assert isinstance(m, fam.Dims) and m.layers == 2 and m.vocab == 256
    runmod.check_layout(cfg, fam.make_params(m, SEED))
    assert check.compare(c, SEED, reqs, 26, controls=controls) == want
    assert fam.CALLS == {"dims", "program_want", "make_params", "hidden",
                         "head_stats"}


@pytest.mark.parametrize("family", [None, "nosuch"])
def test_a_config_must_name_a_family_with_a_module(tmp_path, family):
    c = config("tiny_dense")
    if family is None:
        del c["architecture"]["family"]
        looked_for = f"{tmp_path}/<family>.py"
    else:
        c["architecture"]["family"] = family
        looked_for = str(tmp_path / f"{family}.py")
    with pytest.raises((ValueError, FileNotFoundError),
                       match=re.escape(looked_for)):
        spec.load_family(c, families_dir=tmp_path)
    with pytest.raises((ValueError, FileNotFoundError), match=r"\.py"):
        spec.load_family(c)
