"""A CPU rehearsal of one cell at reduced size, shared by the tests."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec  # noqa: E402
from bench import run as runmod  # noqa: E402

HERE = Path(__file__).resolve().parent
SECONDS = 1.5


def tiny_cell(mix: str = "tiny_chat", end_to_end=None,
              per_layer=None) -> spec.Cell:
    """The qwen05b.chat cell's metrics (or those given), on the tiny model
    and a tiny mix of this directory."""
    chat = spec.load_cell("qwen05b.chat")
    return spec.Cell(name="tiny", chips=1,
                     config=spec.load_json(HERE / "tiny_dense.json"),
                     mix=spec.load_mix(mix, HERE),
                     end_to_end=chat.end_to_end if end_to_end is None
                     else end_to_end,
                     per_layer=chat.per_layer if per_layer is None
                     else per_layer)


def rehearse(seed: int, trace: bool = False, engine_hook=None,
             out: dict | None = None, cell: spec.Cell | None = None) -> dict:
    """run_cell on the CPU, with the harness's look for a chip skipped."""
    return runmod.run_cell(cell or tiny_cell(), seed, SECONDS, trace,
                           require_accelerator=False, compile_cache=False,
                           engine_hook=engine_hook, out=out,
                           log=lambda msg: None)
