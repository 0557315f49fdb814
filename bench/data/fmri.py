"""Resting-state fMRI parcel time series, one subject per request.

Each subject's X is (frames x parcels): every parcel's signal is a loading
on its network's factor, a loading on its system's factor (the Yeo
7-network system the 17-network label belongs to) and parcel noise; columns
are standardised, as connectivity pipelines z-score parcel series before
estimating partial correlations.  Within-network correlations are the
strongest, networks of one system correlate more weakly, and systems are
independent, so a descending lambda first splits the cortex into pieces of
networks, then whole networks, then systems.

The subjects form a bank drawn from ``base_seed`` (a key of the
configuration): subject k's data and its lambda are fixed.  ``--seed``
draws the order in which the bank is served and, per request, a relabelling
of the parcels; every seed serves the same problems in another order.
"""

from __future__ import annotations

import numpy as np


def network_of(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """(network index, system index) of every parcel, in parcel order."""
    nets = cfg["assumed"]["networks"]
    sizes = [int(nets[k]["parcels"]) for k in sorted(nets)]
    if sum(sizes) != int(cfg["n_parcels"]):
        raise ValueError(f"networks hold {sum(sizes)} parcels, not {cfg['n_parcels']}")
    systems = sorted({nets[k]["system"] for k in nets})
    net = np.repeat(np.arange(len(sizes)), sizes)
    sys_of_net = np.array([systems.index(nets[k]["system"]) for k in sorted(nets)])
    return net, sys_of_net[net]


def subject(cfg: dict, k: int) -> np.ndarray:
    """Subject k of the bank: (frames, parcels) float32, columns z-scored."""
    a = cfg["assumed"]
    n, p = int(cfg["n_frames"]), int(cfg["n_parcels"])
    net, system = network_of(cfg)
    rng = np.random.default_rng([int(a["base_seed"]), int(k)])
    Zn = rng.standard_normal((n, int(net.max()) + 1), dtype=np.float32)
    Zs = rng.standard_normal((n, int(system.max()) + 1), dtype=np.float32)
    bn = rng.uniform(*a["network_loading_range"], size=p).astype(np.float32)
    bs = rng.uniform(*a["system_loading_range"], size=p).astype(np.float32)
    X = Zn[:, net] * bn + Zs[:, system] * bs
    X += rng.standard_normal((n, p), dtype=np.float32) * np.float32(a["parcel_noise"])
    X -= X.mean(axis=0)
    X /= X.std(axis=0)
    return X


def bank(cfg: dict, lambdas, count: int) -> list[tuple[np.ndarray, float]]:
    """The first ``count`` subjects with their lambdas (subject k takes
    lambdas[k % len(lambdas)])."""
    return [(subject(cfg, k), float(lambdas[k % len(lambdas)])) for k in range(count)]


def relabel(X: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """One request's parcel order, drawn from the run's generator."""
    return np.ascontiguousarray(X[:, rng.permutation(X.shape[1])])
