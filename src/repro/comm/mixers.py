"""The consensus factories: every mixer is a Topology × Transport × Wire stack.

Four factories are the only way to build a consensus operator.  Each
returns a :class:`repro.comm.composed.ComposedMixer` labelled with the
device scope ``obs:consensus/<name>`` of its lowering:

=======================================  ====================================
factory                                  stack (``name``)
=======================================  ====================================
``make_dense_mixer(w)``                  Static × Dense × wire (``dense``)
``make_dense_mixer(None, schedule=s)``   Scheduled(s ∘ faults) × Dense × wire
``make_gossip_mixer(decomp, mesh, ..)``  — × Gossip(decomp) × wire
                                         (``gossip``; ± ``replica_axis``)
``make_gossip_mixer(None, .., sched=s)`` Scheduled(s ∘ faults) ×
                                         Gossip(s.decomposition()) × wire
``make_hub_mixer(k)``                    Star × Star × Identity (``hub``);
                                         compressed: Star × Dense × codec
``make_identity_mixer()``                — × — × Identity (``identity``)
=======================================  ====================================

The wire is chosen in one place, :func:`repro.comm.wire.make_wire`.

Compression.  Plain mixing sends full-precision parameters; quantizing them
naively stalls consensus at the quantization noise floor, because the
message magnitude stays O(‖θ‖) while the disagreement shrinks.  With
``error_feedback=True`` (the default) we instead gossip *innovations*
(CHOCO-style): every node keeps a public copy θ̂_i that all of its
neighbors can reconstruct, transmits only the compressed innovation, and
applies the consensus correction against the public copies:

    q_i = C(θ_i − θ̂_i),   θ̂_i ← θ̂_i + q_i,
    θ_i ← θ_i + γ·(Σ_j W_ij θ̂_j − θ̂_i).

The *error-feedback residual* of this scheme is e_i = θ_i − θ̂_i: exactly the
mass compression dropped so far, re-offered to the compressor every round
(see :func:`repro.comm.wire.ef_residual`).  Because W is doubly stochastic
the node *average* is preserved exactly no matter how lossy C is, and since
the transmitted innovation shrinks with the disagreement, the relative
compression error per round stays constant and consensus contracts
geometrically (Koloskova et al., 2019).  ``error_feedback=False`` is the
naive memoryless scheme — kept as the ablation baseline that stalls at the
quantization noise floor.

The dense codec round einsums over the public copies (the payload is
*accounted*, the math is identical — the simulation lowering); the gossip
codec round ppermutes the actual compressed payload per matching and the
receiver dequantize-accumulates into its running mix cache
s_i = Σ_j W_ij θ̂_j, never materializing a full-precision wire buffer.
Both derive one key per (node, leaf) as
``fold_in(fold_in(round_key, global_node_index), leaf_idx)``, so they
produce bit-identical stochastic rounding at a fixed seed regardless of
sharding (anchored by ``tests/data/mixer_anchors.json``).

Time-varying graphs.  A ``schedule``
(:class:`~repro.dynamics.schedule.TopologySchedule`) makes the round's W a
*traced operand* — fault-masked by ``faults``
(:class:`~repro.dynamics.faults.FaultConfig`) — so a whole
dropout/straggler sweep compiles ONE program per configuration.  The
gossip lowering freezes the edge coloring of the schedule's union support
and gathers each round's weights and {0, 1} link masks out of W_r
(:func:`repro.comm.topology.gather_round_vectors`).  These stacks count
*active directed links* × the per-node payload each round (traced
``wire_bits``), so a round whose links are all down bills 0 bytes; the
static dense stack keeps its every-node-injects-once estimate.  EF over a
moving W is explained at :class:`repro.comm.wire.ChocoWire` and
:class:`repro.comm.wire.RebaseClock`.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.comm.composed import ComposedMixer
from repro.comm.compressors import CompressionConfig
from repro.comm.topology import ScheduledTopology, StarTopology, StaticTopology
from repro.comm.transport import DenseTransport, GossipTransport, StarTransport
from repro.comm.wire import IdentityWire, MaskedQuantWire, make_wire

AxisName = str | tuple[str, ...]


def _stack(name: str, topology, transport, wire) -> ComposedMixer:
    mixer = ComposedMixer(topology, transport, wire)
    mixer.name = name
    return mixer


def _scheduled(schedule, faults, given, what: str):
    """The topology of a stack: None (static) or the schedule ∘ faults."""
    if schedule is None:
        if faults is not None and faults.enabled:
            raise ValueError("faults replay on a schedule — pass "
                             "schedule=StaticSchedule(w) to fault a fixed W")
        return None
    if given is not None:
        raise ValueError(f"a schedule supplies the topology — pass "
                         f"{what}=None with schedule=")
    return ScheduledTopology(schedule, faults)


def make_dense_mixer(w, compute_dtype=jnp.float32,
                     compression: CompressionConfig | None = None, *,
                     schedule=None, faults=None) -> ComposedMixer:
    """θ_i ← Σ_j W_ij θ_j by einsum along the leading node axis.

    Runs anywhere (CPU simulation with any K); under pjit it lowers to an
    all-gather of O(K·P) bytes — the paper-faithful baseline.  ``w`` is the
    static doubly-stochastic matrix, or None when ``schedule`` supplies a
    traced per-round W (fault-masked by ``faults``).  ``compute_dtype`` is
    the einsum dtype of a full-precision round; codec rounds mix their
    public copies in f32.
    """
    topology = _scheduled(schedule, faults, w, "w")
    if topology is None:
        topology = StaticTopology(w)
    wire = make_wire(compression, topology=topology)
    if not isinstance(wire, IdentityWire):
        compute_dtype = jnp.float32
    return _stack("dense", topology, DenseTransport(compute_dtype), wire)


def make_gossip_mixer(decomp, mesh, node_axis: AxisName, param_specs,
                      compression: CompressionConfig | None = None, *,
                      replica_axis: str | None = None, schedule=None,
                      faults=None, ef_rebase_every: int = 8,
                      ef_rebase_threshold: float = 0.0) -> ComposedMixer:
    """Gossip: shard_map + one ``lax.ppermute`` per graph matching.

    O(deg·P) bytes; the matchings of a ring/torus map onto the physical
    neighbor links of the TPU interconnect.  Needs K == prod(mesh node
    axes).  ``param_specs`` is a pytree of PartitionSpecs matching the
    node-stacked params (leading dim over ``node_axis``), so
    tensor-parallel dims stay sharded.  ``replica_axis`` psum-averages the
    replicas inside each node before the round (FSDP-inside /
    gossip-across, so K ≪ world size).  With ``schedule`` the edge coloring
    is the schedule's union support (``decomp`` is then None) and each
    round's weights are traced; EF compression then re-bases its mix cache
    every ``ef_rebase_every`` rounds, or by ``ef_rebase_threshold``
    (:class:`repro.comm.wire.RebaseClock`).
    """
    topology = _scheduled(schedule, faults, decomp, "decomp")
    if topology is not None:
        decomp = schedule.decomposition()
    transport = GossipTransport(decomp, mesh, node_axis, param_specs,
                                replica_axis=replica_axis)
    wire = make_wire(compression, topology=topology, gossip=True,
                     ef_rebase_every=ef_rebase_every,
                     ef_rebase_threshold=ef_rebase_threshold)
    if isinstance(wire, MaskedQuantWire) and replica_axis is not None:
        raise ValueError(
            "the masked quant wire has no replica average — "
            "drop replica_axis or use the error-feedback wire")
    return _stack("gossip", topology, transport, wire)


def make_hub_mixer(k: int, compression: CompressionConfig | None = None
                   ) -> ComposedMixer:
    """Hub-and-spoke (federated) consensus: the exact global average.

    Every round each node uploads its block and downloads the mean
    (W = 11ᵀ/K, the ρ = 0 endpoint of the mixing-rate axis).  Under
    ``repro.dynamics.LocalUpdateMixer`` with H local steps this is FedAvg;
    adding ``gradient_tracking=True`` makes the tracker correction exactly
    SCAFFOLD's control variate.  The compressed hub rides the dense
    transport with the star W: its codec round re-mixes the full
    public-copy matrix, which under W = 11ᵀ/K is exactly "the server
    averages the reconstructed client innovations".
    """
    topology = StarTopology(k)
    wire = make_wire(compression, topology=topology)
    if isinstance(wire, IdentityWire):
        return _stack("hub", topology, StarTransport(k), wire)
    return _stack("hub", topology, DenseTransport(), wire)


def make_identity_mixer() -> ComposedMixer:
    """No communication — for ablations (pure local SGD)."""
    return _stack("identity", None, None, IdentityWire())
