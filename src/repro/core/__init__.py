"""Core n-TangentProp: jets, Faa di Bruno tables, activation derivative
stacks, the compositional jet-module layer, jet-traceable networks, and the
derivative-engine hierarchy."""

from . import jet, modules
from .activations import TAYLOR_STACKS, tanh_taylor_stack
from .engines import (AutodiffEngine, DerivativeEngine, EngineSpec,
                      JaxJetEngine, NTPEngine)
from .jet import Jet
from .modules import (Activation, Attention, CoordinateEmbedding, Dense,
                      FourierFeatures, MLPBlock, Module, PseudoSequence,
                      Residual, RMSNorm, SelfAttention, Sequential, TokenPool,
                      Wave, make_module, module_names, register_module)
from .network import (DenseMLP, MLP, FourierFeatureMLP, Network, PINNsFormer,
                      ResidualMLP, Transformer, make_network, network_names,
                      register_network, token_points)
from .ntp import MLPParams, init_mlp, num_params
from .partitions import (bell_number, faa_di_bruno_table, partition_count,
                         partitions, raw_bell_coefficient, total_fdb_terms)

__all__ = [
    "jet", "Jet", "modules", "TAYLOR_STACKS", "tanh_taylor_stack",
    "AutodiffEngine", "DerivativeEngine", "EngineSpec", "JaxJetEngine",
    "NTPEngine",
    "Activation", "Attention", "CoordinateEmbedding", "Dense",
    "FourierFeatures", "MLPBlock", "Module", "PseudoSequence", "Residual",
    "RMSNorm", "SelfAttention", "Sequential", "TokenPool", "Wave",
    "make_module", "module_names", "register_module",
    "DenseMLP", "MLP", "FourierFeatureMLP", "Network", "PINNsFormer",
    "ResidualMLP", "Transformer", "make_network", "network_names",
    "register_network", "token_points",
    "MLPParams", "init_mlp", "num_params",
    "bell_number", "faa_di_bruno_table", "partition_count", "partitions",
    "raw_bell_coefficient", "total_fdb_terms",
]
