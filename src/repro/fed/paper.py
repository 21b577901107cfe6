"""The paper's §VI-A experiment as ``FEELTrainer`` arguments."""
from __future__ import annotations

import jax

from ..core import default_system
from ..data import SyntheticImages, non_iid_split
from ..models import cnn
from .rounds import FEELConfig


def paper_setup(side: int = 28, d_hat: int = 200, scheme: str = "proposed",
                selection: str = "faithful", mislabel: float = 0.1):
    """``(sys, data, model, params, cfg)`` for ``FEELTrainer``.

    K=10 devices holding one class each with 600 samples, N=5 RBs, Q=2,
    the paper's CNN (conv 10/20, fc 120/84) with seed-0 weights.  The
    defaults are the paper's sizes: 28x28 images, |D̂_k|=200.
    """
    train = SyntheticImages.make(6000, side=side, seed=0)
    test = SyntheticImages.make(1500, side=side, seed=1)
    data = non_iid_split(train, test, K=10, per_device=600,
                         mislabel_prop=mislabel, seed=0)
    sys_ = default_system(K=10, N=5, Q=2, D_hat=d_hat)
    cfg = FEELConfig(scheme=scheme, d_hat=d_hat, selection_method=selection,
                     eval_every=10)
    params = cnn.init(jax.random.PRNGKey(0), cnn.CNNConfig(side=side))
    return sys_, data, cnn, params, cfg
