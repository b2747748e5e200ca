"""Operations and bytes of the held experts' grouped matmul (the MoE
layer's dispatch of (token, expert) pairs sorted by expert, one weight
[D, F] or [F, D] per expert), computed from shapes."""
from __future__ import annotations


def grouped_matmul(rows: int, d: int, f: int, groups: int,
                   itemsize: int = 2) -> dict:
    """One grouped matmul over ``rows`` pairs with ``groups`` experts'
    weights of d x f: 2·rows·d·f operations; reads the rows (rows·d) and
    every expert's weight (groups·d·f), writes rows·f, at ``itemsize``
    bytes (bfloat16). The same for the weight gradient's form (rows·d and
    rows·f read, groups·d·f written)."""
    return {"ops": 2.0 * rows * d * f,
            "bytes": float(itemsize * (rows * d + groups * d * f
                                       + rows * f))}
