"""``{"kind": "random", "p": 0.1}``: each (source, destination) pair is a
synapse with probability ``p``, drawn from the network's generator as one
uniform matrix of the pair of layers (the Random network of SNEAP's
Table 1)."""
import numpy as np


def connect(spec: dict, n_src: int, n_dst: int,
            rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    return np.nonzero(rng.random((n_src, n_dst)) < float(spec["p"]))
