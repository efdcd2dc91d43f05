"""Compressed-gossip communication subsystem.

protocol.py    — the uniform :class:`Mixer` protocol every consensus
                 operator implements (``mix(theta, CommState, *, round)``),
                 :class:`CommState` and its :class:`CommMetrics` view.
compressors.py — wire codecs (bf16 / int8 / int4 stochastic rounding /
                 topk / randk) behind the :class:`Compressor` protocol,
                 with traced dynamic-rate support.
schedule.py    — :class:`CompressionSchedule`: anneal the codec rate
                 (int8→int4, topk ratio) during training, driven by the
                 round counter or the error-feedback innovation norm.

The consensus stack itself is three composable layers behind one operator:

topology.py    — WHO talks to whom this round: ``round_w(rounds)``
                 providers (static graph / schedule ∘ fault replay / star).
transport.py   — HOW payloads move: dense einsum, shard_map + ppermute
                 gossip (± hierarchical replica psum), hub/star mean.
wire.py        — WHAT crosses each link: identity, memoryless codec,
                 CHOCO error feedback (± delta/re-base clock), masked
                 int8/int4 Pallas — each owning its ``CommState`` fields.
composed.py    — :class:`ComposedMixer`: one consensus operator over a
                 (topology, transport, wire) stack.
mixers.py      — the four factories that build every stack:
                 ``make_dense_mixer`` / ``make_gossip_mixer`` /
                 ``make_hub_mixer`` / ``make_identity_mixer``.

The fused Pallas quantize/dequantize-accumulate kernel lives in
``repro.kernels.quant_gossip`` and plugs in via
``CompressionConfig(use_kernel=True)``.
"""

from repro.comm.composed import ComposedMixer
from repro.comm.compressors import (
    BF16Compressor,
    CompressionConfig,
    Compressor,
    IntQuantizer,
    KernelInt8Quantizer,
    NoCompressor,
    RandKCompressor,
    TopKCompressor,
    fold_leaf,
    make_compressor,
    per_node_keys,
    quant_bits,
)
from repro.comm.mixers import (
    make_dense_mixer,
    make_gossip_mixer,
    make_hub_mixer,
    make_identity_mixer,
)
from repro.comm.protocol import (
    CommMetrics,
    CommState,
    Mixer,
    trivial_comm_state,
    trivial_state_specs,
)
from repro.comm.schedule import CompressionSchedule, ScheduleConfig
from repro.comm.topology import (
    ScheduledTopology,
    StarTopology,
    StaticTopology,
    Topology,
)
from repro.comm.transport import (
    DenseTransport,
    GossipTransport,
    StarTransport,
    Transport,
)
from repro.comm.wire import (
    ChocoWire,
    CodecWire,
    IdentityWire,
    MaskedQuantWire,
    RebaseClock,
    Wire,
    ef_residual,
    make_wire,
)

__all__ = [
    "CompressionConfig", "Compressor", "make_compressor",
    "NoCompressor", "BF16Compressor", "IntQuantizer", "KernelInt8Quantizer",
    "TopKCompressor", "RandKCompressor",
    "Mixer", "CommMetrics", "CommState",
    "trivial_comm_state", "trivial_state_specs",
    "make_dense_mixer", "make_gossip_mixer", "make_hub_mixer",
    "make_identity_mixer",
    "ef_residual", "per_node_keys", "fold_leaf", "quant_bits",
    "ScheduleConfig", "CompressionSchedule",
    # layer API
    "ComposedMixer",
    "Topology", "StaticTopology", "ScheduledTopology", "StarTopology",
    "Transport", "DenseTransport", "GossipTransport", "StarTransport",
    "Wire", "IdentityWire", "CodecWire", "ChocoWire", "MaskedQuantWire",
    "RebaseClock", "make_wire",
]
