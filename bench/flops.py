"""Operations a step requires, counted from its shapes.

A multiply and an add count as two operations.  Only what the
algorithm needs is counted: not what an implementation recomputes, and
not data-dependent work whose amount the shapes do not fix.
"""


def paper_round_flops(m: int, n_worker: int, n_pooled: int, d: int) -> int:
    """One round of Algorithm 1 on the paper runtime.

    Each of the ``m`` workers forms its Hessian ``Xᵀ D X`` (2·n·d²) and
    its gradient (forward and backward, 4·n·d); the run loop then takes the
    loss and the gradient on the pooled data (6·N·d).  Left out: the
    cubic sub-problem's matrix-vector products, whose number is set by
    the data (Algorithm 2 stops when its gradient falls under the
    tolerance), and the per-round test evaluation.
    """
    hessian = 2 * n_worker * d * d
    gradient = 4 * n_worker * d
    return m * (hessian + gradient) + 6 * n_pooled * d
