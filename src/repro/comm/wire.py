"""Wire layer: what crosses each link, and the ``CommState`` fields it owns.

One of the three composable consensus layers (see ``comm/composed.py``).
The wire decides the *payload semantics* of a consensus round and declares
— via ``init_fields``/``spec_fields`` — exactly the ``CommState`` fields
that semantics needs.  ``ComposedMixer`` splices the declared fields over
the trivial state, so adding a wire never perturbs fields it does not own
(the RPR005 discipline, per layer).

:class:`IdentityWire`    — full-precision parameters; trivial state.
:class:`CodecWire`       — memoryless codec: C(θ) crosses the wire every
                           round (the stall ablation).  Owns ``key`` and
                           the codec-rate schedule fields.
:class:`ChocoWire`       — CHOCO error feedback: compressed *innovations*
                           against public copies θ̂.  Owns ``hat`` (and
                           ``hat_mix`` on incremental transports); with a
                           :class:`RebaseClock` also the ``ef_rounds`` /
                           ``ef_drift`` delta/re-base clock of the dynamic
                           gossip stack.
:class:`MaskedQuantWire` — the memoryless masked int8/int4 Pallas wire of
                           the dynamic gossip transport (fused
                           quantize→ppermute→dequant-accumulate kernels).

The codec math here (``encode_leaf`` and friends) is the frozen
pre-refactor ``_CompressedMixerBase`` path, bit-for-bit — the
equivalence-matrix anchors (``tests/data/mixer_anchors.json``) gate it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.comm.compressors import CompressionConfig, make_compressor
from repro.comm.protocol import CommState
from repro.comm.schedule import CompressionSchedule


def ef_residual(theta, state: CommState):
    """The error-feedback residual e = θ − θ̂ (what compression still owes)."""
    if state.hat == ():
        raise ValueError("memoryless mixer (error_feedback=False) "
                         "keeps no residual")
    return jax.tree.map(
        lambda x, h: x.astype(jnp.float32) - h, theta, state.hat)


def _f32_zeros_like(tree):
    # zeros_like keeps each leaf's sharding: zeros(x.shape) would build
    # every node's copy on the default device
    return jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tree)


def _send_mask(masks):
    """Per-node "any live outgoing link this round" vector: ∨ over the
    per-matching link masks.  A node with every incident link down emits a
    zero payload and its θ̂ stays frozen (nobody could apply the delta)."""
    send = masks[0]
    for m in masks[1:]:
        send = jnp.maximum(send, m)
    return send


def _codec_wire_dtypes(compressor, d: int) -> dict[str, int]:
    """Physical per-node wire bytes of one encoded leaf, split by HLO dtype.

    The payload a gossip round ppermutes: the quantized values ride as
    ``s8`` (nibble-packed into half the bytes on the static int4 path),
    scales as ``f32``; topk/randk move (f32 values, s32 indices); bf16
    moves the cast tensor.  This is the per-dtype truth the HLO auditor
    checks collective-permute ops against (``Mixer.wire_dtype_bytes``).
    """
    total = compressor.payload_bytes(d)
    name = getattr(compressor, "name", "")
    if name.startswith("int"):  # int8 / int4 / int8-kernel
        q = compressor.value_bytes(d)
        return {"s8": q, "f32": total - q}
    if name in ("topk", "randk"):
        return {"f32": total // 2, "s32": total // 2}
    if name == "bf16":
        return {"bf16": total}
    return {"f32": total}


def _merge_dtype_bytes(*dicts, scale: float = 1.0) -> dict[str, float]:
    out: dict[str, float] = {}
    for d in dicts:
        for dt, b in d.items():
            out[dt] = out.get(dt, 0.0) + scale * b
    return out


def _leaf_payload_bytes(compressor, params, k: int) -> int:
    """Per-round payload bytes one node injects (sum over leaves).

    ``params`` must be the *global* node-stacked view; the per-node leaf
    size is ``x.size // k`` with ``k`` the mixer's node count, not the
    leaf's own leading dim — a leaf sharded over extra mesh axes (tensor
    parallel, fsdp) or a multi-axis node dimension would otherwise make the
    divisor whatever the local leading extent happens to be and silently
    skew the fig7/fig8 bytes axes.
    """
    total = 0
    for x in jax.tree.leaves(params):
        total += compressor.payload_bytes(x.size // k)
    return total


@dataclasses.dataclass(frozen=True)
class RebaseClock:
    """The delta/re-base cadence of the dynamic EF gossip stack.

    every:     B — re-base the incremental ``hat_mix`` cache from
               full-precision public copies every B-th executed consensus
               round (``ef_rounds % B == B − 1``).  0 = never (static
               fault-free schedules only), 1 = every round.
    threshold: > 0 replaces the fixed clock with the drift proxy
               ‖s − W_r θ̂‖_F measured each round (adaptive re-base; the
               measurement lands in ``CommState.ef_drift``).
    """

    every: int = 8
    threshold: float = 0.0

    @property
    def adaptive(self) -> bool:
        return self.threshold > 0


class Wire:
    """Payload-semantics layer base: trivial state, no codec.

    ``init_fields(params, incremental=...)`` returns the ``CommState``
    fields this wire owns (spliced over ``trivial_comm_state()`` by
    ``ComposedMixer.init_state``); ``spec_fields`` the matching
    PartitionSpecs for the non-trivially-replicated ones.  ``incremental``
    is True on transports that keep the receiver-side running mix cache
    (gossip), where EF wires additionally own ``hat_mix``.
    """

    traced_wire = False
    compression: CompressionConfig | None = None
    ef = False

    def init_fields(self, params, incremental: bool = False) -> dict:
        return {}

    def spec_fields(self, param_specs, incremental: bool = False) -> dict:
        return {}

    def rate(self, state: CommState):
        """Traced codec rate for the round about to run (None = static)."""
        return None


class IdentityWire(Wire):
    """Full-precision payloads — the uncompressed mixers' wire."""


class CodecWire(Wire):
    """Memoryless codec wire: C(θ) crosses every round (the ablation that
    stalls at the quantization noise floor — see ``comm/mixers.py``)."""

    ef = False

    def __init__(self, compression: CompressionConfig):
        self.compression = compression
        self.compressor = make_compressor(compression)
        self.gamma = compression.resolved_gamma
        self.schedule = (
            CompressionSchedule(compression.schedule, compression.kind,
                                compression.ratio)
            if compression.schedule is not None else None)

    @property
    def traced_wire(self) -> bool:
        return self.schedule is not None

    # -- state ----------------------------------------------------------------

    def init_fields(self, params, incremental: bool = False) -> dict:
        return {"key": jax.random.PRNGKey(self.compression.seed)}

    def spec_fields(self, param_specs, incremental: bool = False) -> dict:
        return {}

    # -- schedule / accounting -------------------------------------------------

    def rate(self, state: CommState):
        if self.schedule is None:
            return None
        return self.schedule.rate(state.rounds, state.res_norm, state.res_ref)

    def gamma_for(self, rate):
        """Per-round consensus step size: the static config-resolved γ, or
        γ damped with an annealed sparsifier rate
        (``ScheduleConfig.damp_gamma`` — traced min(γ, 2·rate))."""
        if self.schedule is None:
            return self.gamma
        return self.schedule.gamma_for(self.gamma, rate)

    def next_sched_state(self, state: CommState, res_norm):
        """(res_norm', res_ref', rounds') after a round observing res_norm."""
        res_ref = (self.schedule.update_ref(state.rounds, res_norm,
                                            state.res_ref)
                   if self.schedule is not None else state.res_ref)
        return res_norm, res_ref, state.rounds + 1

    def round_wire_bits(self, params, rate, senders, k: int):
        """Traced wire bits one round injects: senders × per-node payload."""
        per_node = 0.0
        for x in jax.tree.leaves(params):
            per_node = per_node + self.compressor.payload_bits(
                x.size // k, rate)
        return jnp.asarray(senders * per_node, jnp.float32)

    # -- the per-leaf codec step ----------------------------------------------

    def compress_block(self, x, keys, rate, send_mask=None):
        """Encode one (K_local, d) block, optionally sender-masked.

        ``send_mask`` (K_local,) in {0, 1} is the dynamic lowering's
        per-round "this node has at least one live link" vector: masked rows
        emit a zero payload (nothing crosses the wire, their θ̂ stays
        frozen).  The kernel quantizer serves it with the fused masked
        Pallas kernel; other codecs mask the input block, which encodes to
        an all-zero payload.  ``send_mask=None`` (static lowerings) and an
        all-ones mask are bit-identical to the unmasked encode.
        """
        if send_mask is None:
            return self.compressor.compress(x, keys, rate)
        masked = getattr(self.compressor, "compress_masked", None)
        if masked is not None:
            return masked(x, keys, send_mask, rate)
        return self.compressor.compress(x * send_mask[:, None], keys, rate)

    def encode_leaf(self, x, hat, keys, rate, send_mask=None):
        """Compress one flattened leaf.

        Returns (payload, public', hat') where ``public'`` is this node's
        new publicly-reconstructible value (θ̂' in EF mode, C(θ) memoryless)
        and ``hat'`` is the state to carry (θ̂' or ()).  ``keys`` is one PRNG
        key per node row; ``rate`` the traced schedule rate (or None);
        ``send_mask`` the dynamic lowerings' sender mask (see
        :meth:`compress_block`).
        """
        with jax.named_scope("obs:codec/encode"):
            if self.ef:
                payload = self.compress_block(x - hat, keys, rate, send_mask)
                qhat = self.compressor.decompress(payload, x.shape[1])
                new_hat = hat + qhat
                return payload, new_hat, new_hat
            payload = self.compress_block(x, keys, rate, send_mask)
            public = self.compressor.decompress(payload, x.shape[1])
            return payload, public, ()


class ChocoWire(CodecWire):
    """CHOCO error-feedback wire: compressed innovations against θ̂.

    Owns ``hat`` (the public copies — the EF residual is θ − θ̂), plus
    ``hat_mix`` on incremental transports (the receiver-side running mix
    s_i = Σ_j W_ij θ̂_j of the gossip lowering).  With a
    :class:`RebaseClock` it additionally owns the ``ef_rounds`` consensus
    clock (and ``ef_drift`` in adaptive mode) that selects delta vs
    full-precision re-base rounds on the dynamic gossip stack.
    """

    ef = True

    def __init__(self, compression: CompressionConfig,
                 clock: RebaseClock | None = None):
        if not compression.error_feedback:
            raise ValueError(
                "ChocoWire is the error-feedback wire — build CodecWire "
                "for the memoryless (error_feedback=False) ablation")
        super().__init__(compression)
        self.clock = clock

    def init_fields(self, params, incremental: bool = False) -> dict:
        fields = {"hat": _f32_zeros_like(params),
                  "key": jax.random.PRNGKey(self.compression.seed)}
        if incremental:
            fields["hat_mix"] = _f32_zeros_like(params)
        if self.clock is not None:
            fields["ef_rounds"] = jnp.int32(0)
            if self.clock.adaptive:
                fields["ef_drift"] = jnp.float32(0.0)
        return fields

    def spec_fields(self, param_specs, incremental: bool = False) -> dict:
        rep = jax.sharding.PartitionSpec()
        fields = {"hat": param_specs}
        if incremental:
            fields["hat_mix"] = param_specs
        if self.clock is not None:
            fields["ef_rounds"] = rep
            if self.clock.adaptive:
                fields["ef_drift"] = rep
        return fields


class MaskedQuantWire(Wire):
    """Memoryless masked int8/int4 quantization for the dynamic gossip
    transport: each matching runs the fused masked Pallas kernels,
    quantize(mask) → ppermute(int8 payload + scales) → masked
    dequantize-accumulate, with a fresh C(θ) every round (the int4 rate
    rides the int8 container at a traced qmax).  Owns only ``key``.
    """

    ef = False

    def __init__(self, quantized: CompressionConfig):
        if quantized.kind not in ("int8", "int4"):
            raise ValueError(
                "the masked quant_gossip wire serves kind='int8' or "
                "'int4' (the traced-qmax rate in the int8 container)")
        if quantized.schedule is not None:
            raise ValueError(
                "rate schedules are not supported on the masked wire")
        self.quantized = quantized
        self.compression = quantized
        # int4 rides the int8 container at qmax=7 (the masked kernel's
        # traced rate); payload accounting bills the effective bits,
        # like the scheduled-rate static path
        self._qmax = 127 if quantized.kind == "int8" else 7
        from repro.comm.compressors import KernelInt8Quantizer

        self.compressor = KernelInt8Quantizer(
            quantized.block_d, quantized.interpret)

    def init_fields(self, params, incremental: bool = False) -> dict:
        return {"key": jax.random.PRNGKey(self.quantized.seed)}

    def leaf_bits(self, d: int) -> float:
        """Effective wire bits per node for one leaf: ceil(log2(2qmax+1))
        per entry — 8 for int8, 4 for the int4 rate riding the int8
        container (what a bit-packing transport moves) — plus the
        per-(node, block) f32 scales.  Pure python (this is called from a
        traced context; staging a constant would leak a tracer)."""
        import math

        bits = math.ceil(math.log2(2 * self._qmax + 1))
        # d is a leaf .size — host int, see docstring
        return float(bits * d + 32 * self.compressor._n_blocks(d))  # repro: noqa[RPR002]


def make_codec_wire(compression: CompressionConfig,
                    clock: RebaseClock | None = None) -> CodecWire:
    """The EF/memoryless split the legacy compressed mixers encoded in a
    flag: ``error_feedback=True`` → :class:`ChocoWire` (+ optional clock),
    False → :class:`CodecWire`."""
    if compression.error_feedback:
        return ChocoWire(compression, clock=clock)
    if clock is not None:
        raise ValueError("the delta/re-base clock belongs to the "
                         "error-feedback wire")
    return CodecWire(compression)
