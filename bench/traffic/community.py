"""A BFLC training community: its clients' data and its round settings.

The data is FEMNIST-like (the program's ``repro.data.make_femnist_like``,
copied and vectorised so set-up stays short): 62 classes of 28x28 images,
each class a smooth low-frequency prototype; each writer adds a smooth
style field, every sample a random shift of up to two pixels and pixel
noise; writers hold log-normally many samples (at least 8) over all 62
classes in Dirichlet proportions.  A mix file names this generator and
gives the community (``num_clients``, ``mean_samples``, ``alpha``,
``noise``) and the round settings (``round``: the ``BFLCConfig`` fields).
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

NUM_CLASSES = 62
IMG = 28


def _smooth_fields(rng: np.random.Generator, n: int, scale: float,
                   k: int = 4) -> np.ndarray:
    """n random low-frequency 28x28 fields from k x k coefficient grids."""
    coeff = rng.normal(0, scale, (n, k, k))
    yy = np.linspace(0, np.pi, IMG)
    basis = np.stack([np.cos(yy * i) for i in range(k)])        # (k, 28)
    return np.einsum("ki,nkl,lj->nij", basis, coeff, basis)


def make_dataset(mix: Dict[str, Any], seed: int):
    from repro.data.synthetic import FederatedDataset

    rng = np.random.default_rng(seed)
    n = int(mix["num_clients"])
    protos = _smooth_fields(rng, NUM_CLASSES, 1.0)
    protos /= np.abs(protos).max(axis=(1, 2), keepdims=True)
    sizes = np.maximum(8, rng.lognormal(np.log(mix["mean_samples"]), 0.5,
                                        n).astype(int))
    styles = _smooth_fields(rng, n, 0.25)
    probs = rng.dirichlet(np.full(NUM_CLASSES, mix["alpha"]), n)
    total = int(sizes.sum())
    owner = np.repeat(np.arange(n), sizes)
    # inverse-cdf draw of each sample's class from its writer's proportions
    cdf = np.cumsum(probs, axis=1)
    labels = (rng.random(total)[:, None] > cdf[owner]).sum(axis=1)
    labels = np.minimum(labels, NUM_CLASSES - 1).astype(np.int32)
    shifts = rng.integers(-2, 3, size=(total, 2))
    rows = (np.arange(IMG)[None, :] - shifts[:, :1]) % IMG
    cols = (np.arange(IMG)[None, :] - shifts[:, 1:]) % IMG
    imgs = protos[labels[:, None, None], rows[:, :, None], cols[:, None, :]]
    imgs = imgs + styles[owner] + rng.normal(0, mix["noise"], imgs.shape)
    imgs = imgs.astype(np.float32)[..., None]
    bounds = np.cumsum(sizes)[:-1]
    test_n = int(mix.get("test_size", 64))
    test_labels = rng.integers(0, NUM_CLASSES, test_n).astype(np.int32)
    test_imgs = protos[test_labels] + rng.normal(0, mix["noise"],
                                                 (test_n, IMG, IMG))
    return FederatedDataset(
        client_images=np.split(imgs, bounds),
        client_labels=np.split(labels, bounds),
        test_images=test_imgs.astype(np.float32)[..., None],
        test_labels=test_labels)


def round_config(mix: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The runtime's ``BFLCConfig`` fields, its rng seeded from ``seed``."""
    return dict(mix["round"], seed=int(seed))
