"""Operations and bytes of an expert layer's grouped products, from shapes
and the rows that were really routed to the experts held here.

One layer, ``rows`` assignments over ``held`` experts of hidden size ``H``
and width ``F``: three products (gate, up, down).  Forward: ``2 * rows * 3 *
H * F`` operations; each product reads its rows and its experts' weights
and writes its rows once.  Backward: two products for each forward one (the
rows' gradient and the weights'), twice the operations; each reads its two
operands and writes its result once.  The element-wise SiLU and product
between them, the rows no expert here got (the buffers are as long as all
assignments) and anything recomputed are not counted.
"""

from __future__ import annotations


def forward(rows, hidden, width, held, itemsize=2):
    flops = 2 * rows * 3 * hidden * width
    weights = 3 * held * hidden * width
    # gate, up: rows x H in, rows x F out; down: rows x F in, rows x H out
    moved = itemsize * (weights + 3 * rows * hidden + 3 * rows * width)
    return flops, moved


def backward(rows, hidden, width, held, itemsize=2):
    flops = 2 * forward(rows, hidden, width, held)[0]
    weights = 3 * held * hidden * width
    # per product: dY and W in, dX out; X and dY in, dW out
    moved = itemsize * (2 * weights
                        + 3 * 2 * (rows * hidden + rows * width)
                        + 3 * (rows * hidden + rows * width))
    return flops, moved


def step(rows, hidden, width, held, layers, recomputed, itemsize=2):
    """One training step over ``layers`` expert layers; a recomputed forward
    is an execution and is counted as one."""
    f_flops, f_moved = forward(rows, hidden, width, held, itemsize)
    b_flops, b_moved = backward(rows, hidden, width, held, itemsize)
    forwards = 2 if recomputed else 1
    return (layers * (forwards * f_flops + b_flops),
            layers * (forwards * f_moved + b_moved))
