"""Gene-expression matrices shaped like a microarray study.

A latent-factor model with power-law module sizes, after the repository's
``microarray_like`` generator (copied here so the benchmark's inputs cannot
move with the program): genes are split into modules, each module is driven
by one latent factor with per-gene loadings drawn from ``loading_range`` and
a random sign, and every gene carries independent noise of scale ``noise``.
Genes outside all modules are pure noise and sit isolated at moderate
lambda; as lambda falls, the best-loaded genes of each module join first and
the modules grow into the large components of the paper's Figure 1.

The expression values come from one draw at ``base_seed`` (a key of the
configuration).  ``--seed`` only relabels that draw: it permutes the genes
and the samples and flips the sign of a random half of the genes.  So every
seed poses the same screening and solving problem, with the same component
sizes at every lambda, laid out differently over the tiles and the labels;
the work per run stays the same from seed to seed.
"""

from __future__ import annotations

import numpy as np


def base_matrix(cfg: dict) -> np.ndarray:
    """The (n, p) float64 draw every seed relabels."""
    n, p = int(cfg["n_samples"]), int(cfg["n_genes"])
    sizes = [int(s) for s in cfg["assumed"]["module_sizes"]]
    lo, hi = cfg["assumed"]["loading_range"]
    if sum(sizes) > p:
        raise ValueError(f"modules hold {sum(sizes)} genes, more than p={p}")
    rng = np.random.default_rng(int(cfg["assumed"]["base_seed"]))
    X = rng.standard_normal((n, p)) * float(cfg["assumed"]["noise"])
    g = 0
    for s in sizes:
        z = rng.standard_normal((n, 1))
        load = rng.uniform(lo, hi, size=(1, s)) * rng.choice([-1.0, 1.0], size=(1, s))
        X[:, g : g + s] += z @ load
        g += s
    return X


def relabel(X: np.ndarray, seed: int) -> np.ndarray:
    """Permute genes and samples and flip signs, all drawn from ``seed``;
    returns float32, the dtype the data is served in."""
    rng = np.random.default_rng(seed)
    n, p = X.shape
    rows = rng.permutation(n)
    cols = rng.permutation(p)
    signs = rng.choice(np.array([-1.0, 1.0]), size=p)
    return (X[rows][:, cols] * signs).astype(np.float32)


def make(cfg: dict, seed: int) -> np.ndarray:
    """The (n, p) float32 expression matrix of one run."""
    return relabel(base_matrix(cfg), seed)
