"""float64 numpy Krum (upstream ML/Pytorch/client_obj.py:114-143,
DistSys/krum.go:100-166): score_i = sum of the n - f - 2 smallest squared
distances from update i to the others; accept the n - f lowest scores.
Copied from chip_smoke.krum_oracle (PR 21) with the precision control."""

import numpy as np


def krum_oracle(x, f, q=None):
    x = np.asarray(x, np.float64)
    n = x.shape[0]
    if q is None:  # float64: no [n, d] temporaries
        sq = np.einsum("ij,ij->i", x, x)
        d = np.maximum(sq[:, None] + sq[None, :] - 2.0 * (x @ x.T), 0.0)
        q = np.asarray
    else:
        x = q(x)
        sq = q(np.sum(q(x * x), axis=1))
        d = q(np.maximum(q(sq[:, None] + sq[None, :])
                         - q(2.0 * q(x @ x.T)), 0.0))
    np.fill_diagonal(d, np.inf)
    scores = q(np.sort(d, axis=1)[:, :max(n - f - 2, 0)].sum(axis=1))
    accept = np.zeros(n, bool)
    accept[np.argsort(scores, kind="stable")[:n - f]] = True
    return scores, accept


def beyond_ties(scores, accept, got, rel_err):
    """Indices where `got` (the program's accept set) differs from the
    oracle's `accept` although the oracle's own score sits further than
    `rel_err` (relative) from the accept/reject cut: a disagreement
    within that band is a tie the score error explains (PR 21,
    chip_smoke.phase_pallas)."""
    keep = int(accept.sum())
    order = np.sort(scores)
    cut = 0.5 * (order[keep - 1] + order[keep])
    differ = np.nonzero(np.asarray(got, bool) != accept)[0]
    return [int(i) for i in differ
            if abs(scores[i] - cut) > rel_err * abs(cut)]
