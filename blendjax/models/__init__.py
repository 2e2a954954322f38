"""Model zoo for streamed-synthetic-data training.

The reference's only models are a 5-layer conv discriminator
(``examples/densityopt/densityopt.py:139-190``) and a hand-tuned
P-controller (``examples/control/cartpole.py:19-21``); blendjax ships
JAX-native equivalents plus the models the TPU train loops need:

- :class:`CubeRegressor` — the benchmark CNN (streamed cube images ->
  corner coordinates), bfloat16 on the MXU.
- :class:`Discriminator` — densityopt's real/fake image critic.
- :class:`PolicyValueNet` — actor-critic MLP for the RL examples.
- :class:`StreamFormer` — a compact vision transformer over image streams
  with optional ring attention (sequence-parallel) and tensor-parallel
  friendly dims; the multi-chip sharding showcase.
- :class:`StreamHybrid` — a stack by pattern string, one mixer a layer:
  Mamba-2 (:class:`Mamba2Mixer`), routed experts without drops as one
  chip's share (:class:`RoutedExperts`), grouped-query attention.
"""

from blendjax.models.cnn import CubeRegressor
from blendjax.models.discriminator import Discriminator
from blendjax.models.hybrid import Mamba2Mixer, StreamHybrid
from blendjax.models.moe import (
    MoEMLP,
    RoutedExperts,
    apply_with_aux,
    collect_aux_loss,
)
from blendjax.models.policy import PolicyValueNet, QNetwork
from blendjax.models.transformer import StreamFormer

__all__ = [
    "CubeRegressor",
    "Discriminator",
    "Mamba2Mixer",
    "MoEMLP",
    "RoutedExperts",
    "apply_with_aux",
    "collect_aux_loss",
    "PolicyValueNet",
    "QNetwork",
    "StreamFormer",
    "StreamHybrid",
]
