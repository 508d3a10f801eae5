"""``{"kind": "local", "radius": r}``: receptive fields on 2D grids, as the
CARLsim image-processing tutorials behind Smooth and Edge build them.  Each
source feeds the (2r+1)^2 block around its position scaled into the
destination grid."""
import math

import numpy as np


def _grid(n: int) -> tuple[int, int]:
    """Near-square (h, w) with h * w == n."""
    h = int(math.sqrt(n))
    while n % h:
        h -= 1
    return h, n // h


def connect(spec: dict, n_src: int, n_dst: int,
            rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    radius = int(spec["radius"])
    hs, ws = _grid(n_src)
    hd, wd = _grid(n_dst)
    src_r, src_c = np.divmod(np.arange(n_src), ws)
    ctr_r = (src_r * hd) // hs
    ctr_c = (src_c * wd) // ws
    srcs, dsts = [], []
    for dr in range(-radius, radius + 1):
        for dc in range(-radius, radius + 1):
            rr, cc = ctr_r + dr, ctr_c + dc
            ok = (rr >= 0) & (rr < hd) & (cc >= 0) & (cc < wd)
            srcs.append(np.nonzero(ok)[0])
            dsts.append(rr[ok] * wd + cc[ok])
    return np.concatenate(srcs), np.concatenate(dsts)
