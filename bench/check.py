"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the engine is freed, a sample of the
requests the window finished, drawn from the seed and holding the longest
of them, is run through the plain float32 reference of the model family
that the configuration names (``bench/families/<family>.py``: its
``hidden`` and ``head_stats``, on its weights from the seed),
teacher-forced on each prompt with its served tokens. Two numbers are
compared:

``logit_gap``    the widest gap by which a served token's reference
                 logit lies below the reference's best at that position
                 (greedy tokens only; the mixes are greedy);
``logprob_err``  the widest |served logprob - reference logprob| of a
                 served token: the engine's fused logit-statistics pass
                 against ``log_softmax`` of the reference logits.

``controls`` names controls, each the reference put in the program's
place with one stage computed one precision below the configuration's,
whose two numbers are read too: at each position of the same prompts and
tokens, the token that the lower precision puts first, and its logprob.

``fp8``        every matmul's inputs rounded to float8 e4m3's 3 mantissa
               bits, one precision below the bfloat16 the configurations
               state for them (the family's ``hidden`` and
               ``head_stats`` with ``rounding="fp8"``);
``bf16_stats`` the f32 logits and the compensated f32 logit statistics
               that the configurations state, computed in bfloat16
               (the family's ``head_stats(..., stats="bf16")``).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from bench import loadgen, spec

ROW_PAD = 128           # served positions are scored in blocks of this size
SEQ_PAD = 128           # sequences are padded to a multiple of this size


@dataclass
class Served:
    rid: int
    prompt: list
    output: list
    logprobs: list


def sample(served: list[Served], seed: int, tokens: int) -> list[Served]:
    """The longest request, then others drawn from the seed, until the
    sample holds at least ``tokens`` served tokens."""
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.prompt) + len(r.output),
                                         r.rid))
    rest = [r for r in served if r is not longest]
    order = loadgen.rng_for(seed, 3).permutation(len(rest))
    out, n = [longest], len(longest.output)
    for i in order:
        if n >= tokens:
            break
        out.append(rest[i])
        n += len(rest[i].output)
    return out


CONTROLS = {"fp8": {"rounding": "fp8"}, "bf16_stats": {"stats": "bf16"}}


def compare(config: dict, seed: int, reqs: list[Served], max_tokens: int,
            controls: tuple = ()) -> dict:
    """The compared numbers over ``reqs``, and under ``"controls"`` those
    of each control named."""
    family = spec.load_family(config)
    m = family.dims(config)
    params = family.make_params(m, seed)
    seq_len = loadgen.ceil_to(max_tokens, SEQ_PAD)
    gaps, errs = [], []
    c_gaps = {c: [] for c in controls}
    c_errs = {c: [] for c in controls}
    n_tokens = 0
    for r in reqs:
        seq = np.zeros(seq_len, np.int32)
        full = list(r.prompt) + list(r.output[:-1])
        seq[:len(full)] = full
        n = len(r.output)
        n_tokens += n
        rows = len(r.prompt) - 1 + np.arange(n)
        ids = np.asarray(r.output, np.int32)
        h = family.hidden(params, seq, m)
        hc = {c: family.hidden(params, seq, m, CONTROLS[c]["rounding"])
              if "rounding" in CONTROLS[c] else h for c in controls}
        for b in range(0, n, ROW_PAD):
            sl = slice(b, min(b + ROW_PAD, n))
            k = sl.stop - sl.start
            rr = np.full(ROW_PAD, rows[sl][-1], np.int32)
            rr[:k] = rows[sl]
            ii = np.zeros(ROW_PAD, np.int32)
            ii[:k] = ids[sl]
            ref = family.head_stats(params, h, rr, ii, m)
            gaps.append((ref["max"] - ref["at"])[:k])
            lp_ref = (ref["at"] - ref["lse"])[:k]
            errs.append(np.abs(np.asarray(r.logprobs[sl.start:sl.stop])
                               - lp_ref))
            for name in controls:
                c = family.head_stats(params, hc[name], rr, ii, m,
                                      **CONTROLS[name])
                a = c["argmax"].astype(np.int32)
                at = family.head_stats(params, h, rr, a, m)["at"]
                c_gaps[name].append((ref["max"] - at)[:k])
                c_errs[name].append(np.abs((c["max"] - c["lse"])
                                           - (at - ref["lse"]))[:k])
    del params
    widest = lambda parts: float(np.max(np.concatenate(parts)))  # noqa: E731
    out = {"tokens": n_tokens, "requests": len(reqs),
           "logit_gap": widest(gaps), "logprob_err": widest(errs)}
    if controls:
        out["controls"] = {c: {"logit_gap": widest(c_gaps[c]),
                               "logprob_err": widest(c_errs[c])}
                           for c in controls}
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit, and whether all hold."""
    compared = {k: {"value": numbers[k], "limit": limits.get(k)}
                for k in ("logit_gap", "logprob_err")}
    ok = all(v["limit"] is not None and np.isfinite(v["value"])
             and v["value"] <= v["limit"] for v in compared.values())
    return ok, compared
