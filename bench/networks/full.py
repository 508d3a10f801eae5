"""``{"kind": "full"}``: every source feeds every destination, as in a fully
connected MLP."""
import numpy as np


def connect(spec: dict, n_src: int, n_dst: int,
            rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    return (np.repeat(np.arange(n_src), n_dst),
            np.tile(np.arange(n_dst), n_src))
