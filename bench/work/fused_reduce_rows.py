"""Row reductions of the logit-statistics pass: [S, V] f32 rows.

The problem is memory bound: read every element of the rows once. Flops
count one operation per element and output (max, sum) and two for the
sum of squares.
"""

from __future__ import annotations

ELEM_BYTES = 4
OPS = {"max": 1, "sum": 1, "sumsq": 2}


def work(rows: int, cols: int, outputs=("sum",)) -> tuple[float, float]:
    """(flops, bytes) of one launch over ``rows`` x ``cols`` f32."""
    n = rows * cols
    return (float(n * sum(OPS[o] for o in outputs)),
            float(n * ELEM_BYTES + rows * len(outputs) * ELEM_BYTES))


def logit_stats(rows: int, cols: int) -> list[tuple[float, float]]:
    """The two launches of one logit-statistics pass: max, sum and sum of
    squares of the logits, then the sum of their exponentials."""
    return [work(rows, cols, ("max", "sum", "sumsq")),
            work(rows, cols, ("sum",))]
