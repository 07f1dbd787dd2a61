"""The timed path broken underneath a CPU rehearsal: each fault that a
one-chip serving cell can have makes ``correct`` come out false. (The
exchange between chips does not exist in a one-chip cell.)"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from rehearsal import rehearse  # noqa: E402

SEED = 2**31 + 999


def state_unchanged(engine):
    """The decode step returns the caches it was given."""
    decode = engine._decode

    def broken(params, tokens, caches):
        rows, packed, _ = decode(params, tokens, caches)
        return rows, packed, caches
    engine._decode = broken


def token_altered(engine):
    """Every decoded token is changed where it is produced."""
    decode = engine._decode
    vocab = engine.cfg.vocab_size

    def broken(params, tokens, caches):
        rows, packed, new = decode(params, tokens, caches)
        return rows, packed.at[0].set((packed[0] + 1) % vocab), new
    engine._decode = broken


def half_batch_left_out(engine):
    """Every other slot is left out of the step: it gets the result it
    got the step before."""
    decode = engine._decode
    last = []

    def broken(params, tokens, caches):
        rows, packed, new = decode(params, tokens, caches)
        if last:
            packed = packed.at[:, 1::2].set(last[-1][:, 1::2])
        last[:] = [packed]
        return rows, packed, new
    engine._decode = broken


@pytest.mark.parametrize("fault", [state_unchanged, token_altered,
                                   half_batch_left_out])
def test_fault_makes_the_run_not_correct(fault):
    res = rehearse(SEED, engine_hook=fault)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["compared"].values())
