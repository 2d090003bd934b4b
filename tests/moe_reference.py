"""The routed part of an expert layer written plainly, for the tests of
`distributed/moe.py` `routed_experts` and of the models that call it:
every token through its chosen experts, one at a time, in numpy
float64."""

import numpy as np


def dense_moe(h, router, bias, gate_up, down, top_k, scale, first=0,
              n_held=None, valid=None):
    """What `routed_experts` must equal: (y [N, D], counts [held]) of
    the experts [first, first + held) over tokens h [N, D]; tokens that
    are not `valid` make no assignment."""
    h, router, gate_up, down = (np.asarray(a, np.float64)
                                for a in (h, router, gate_up, down))
    sig = 1.0 / (1.0 + np.exp(-(h @ router)))
    n_held = gate_up.shape[0] if n_held is None else n_held
    y = np.zeros_like(h)
    counts = np.zeros(n_held, int)
    for t in range(h.shape[0]):
        if valid is not None and not valid[t]:
            continue
        chosen = np.argsort(-(sig[t] + np.asarray(bias)),
                            kind="stable")[:top_k]
        total = sig[t][chosen].sum()
        for e in chosen:
            if first <= e < first + n_held:
                gu = h[t] @ gate_up[e - first]
                f = gu.size // 2
                act = gu[:f] / (1.0 + np.exp(-gu[:f])) * gu[f:]
                y[t] += scale * sig[t][e] / total * (act @ down[e - first])
                counts[e - first] += 1
    return y, counts
