"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import numpy as np


def set_count_less_ref(elements, targets):
    e = np.asarray(elements)
    t = np.asarray(targets)
    return (e[None, :] < t[:, None]).sum(axis=1).astype(np.int32)


def filter_tree_lookup_ref(keys, payloads, targets):
    k = np.asarray(keys)
    p = np.asarray(payloads)
    t = np.asarray(targets)
    out = np.full(t.shape, -1, np.int32)
    hit = np.zeros(t.shape, bool)
    lut = {int(kk): int(pp) for kk, pp in zip(k, p)}
    for i, tt in enumerate(t):
        if int(tt) in lut:
            out[i] = lut[int(tt)]
            hit[i] = True
    return out, hit


def segment_sum_sorted_ref(dst, messages, n_nodes):
    d = np.asarray(dst)
    m = np.asarray(messages)
    out = np.zeros((n_nodes, m.shape[1]), np.float32)
    for e in range(len(d)):
        if 0 <= d[e] < n_nodes:
            out[d[e]] += m[e]
    return out
