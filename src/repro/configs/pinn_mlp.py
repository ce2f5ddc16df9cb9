"""The paper's own architecture: tanh MLP for PINN training (3x24 default).

Not part of the assigned LM pool; registered so --arch pinn-mlp drives the
paper-faithful experiments through the same launcher."""

from .base import ArchConfig

CONFIG = ArchConfig(
    name="pinn-mlp",
    family="pinn",
    n_layers=3,
    d_model=24,          # width
    n_heads=1,
    n_kv_heads=1,
    head_dim=1,
    d_ff=24,
    vocab=1,             # d_in = d_out = 1 (self-similar Burgers profile)
    attn_pattern=("global",),
    dtype="float32",
    source="[paper section IV: 3 hidden layers x 24 neurons, tanh]",
)
