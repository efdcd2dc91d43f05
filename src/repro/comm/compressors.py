"""Wire compressors for the gossip consensus step.

Every compressor maps a node-stacked block ``x`` of shape ``(K, D)`` float32
(one flattened parameter leaf, K local nodes) to a *payload* pytree that is
what actually crosses the interconnect, plus the inverse map.  Per-node
granularity matters: each node quantizes against its own dynamic range, so a
single outlier node cannot destroy every node's resolution.

PRNG contract: ``compress`` takes ``keys`` — a *batched* key array with one
key per node row (see :func:`per_node_keys`) — and draws its stochastic-
rounding / sparsification noise row-by-row from them.  Both consensus
lowerings (dense einsum and shard_map gossip) derive the row keys the same
way, ``fold_in(fold_in(round_key, node), leaf)``, so they agree bit-for-bit
at a fixed seed no matter how the node axis is sharded.

Dynamic rate: ``compress(..., rate=...)`` accepts a *traced* scalar so a
:class:`~repro.comm.schedule.CompressionSchedule` can move the codec rate
every round without recompiling.  For the quantizers ``rate`` is the
quantization ceiling qmax (127 = int8 wire, 7 = int4); the buffer stays
int8-shaped but only ``ceil(log2(2·qmax+1))`` bits per entry carry
information — ``payload_bits`` reports that traced count, which is what a
bit-packing transport moves.  For the sparsifiers ``rate`` is the kept
fraction: the payload buffer is sized for the static ``ratio`` maximum and
entries past the dynamic count are masked (never sent).

Implementations:

* ``NoCompressor``     — identity (float32 wire), the paper baseline.
* ``BF16Compressor``   — round-to-nearest bfloat16 cast, 2 bytes/param.
* ``IntQuantizer``     — QSGD-style int8/int4 uniform quantization with
  *stochastic rounding* (``floor(x/scale + u)``, u ~ U[0,1)), per-node scale.
  Unbiased: E[decompress(compress(x))] = x.  int4 packs two nibbles per int8
  byte so the wire buffer is genuinely half the int8 size (static rate only;
  the dynamic-rate path keeps the unpacked buffer and accounts bits).
* ``TopKCompressor``   — magnitude top-k sparsification per node (biased;
  pair with error feedback).
* ``RandKCompressor``  — uniform random-k sparsification per node.

``make_compressor`` builds one from a :class:`CompressionConfig`; with
``use_kernel=True`` the int8 path is served by the fused Pallas
``quant_gossip`` kernel (see ``repro.kernels.quant_gossip``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.comm.schedule import ScheduleConfig

_SCALE_BYTES = 4  # one float32 scale per node per leaf


def per_node_keys(key: jax.Array, node_ids) -> jax.Array:
    """One independent PRNG key per node row: ``fold_in(key, node_id)``.

    ``node_ids`` are *global* node indices, so a shard holding rows
    [s·k_local, (s+1)·k_local) of the stacked leaf derives exactly the keys
    the dense (unsharded) lowering derives for those rows.
    """
    return jax.vmap(lambda n: jax.random.fold_in(key, n))(
        jnp.asarray(node_ids))


def fold_leaf(keys: jax.Array, leaf_idx: int) -> jax.Array:
    """Fold a static leaf index into a batch of per-node keys."""
    return jax.vmap(lambda kk: jax.random.fold_in(kk, leaf_idx))(keys)


def _uniform_rows(keys: jax.Array, d: int) -> jax.Array:
    """(K,) keys -> (K, d) uniforms, each row drawn from its own key."""
    return jax.vmap(lambda kk: jax.random.uniform(kk, (d,), jnp.float32))(keys)


def quant_bits(qmax) -> jax.Array:
    """Wire bits per entry for a symmetric integer code with ceiling qmax."""
    return jnp.ceil(jnp.log2(2.0 * jnp.asarray(qmax, jnp.float32) + 1.0))


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """End-to-end compression knobs, threaded from CLI to kernels.

    Attributes:
      kind: "none" | "bf16" | "int8" | "int4" | "topk" | "randk".
      ratio: kept fraction for topk/randk (of each leaf's per-node size).
        With a schedule this is the *maximum* (buffer-sizing) fraction.
      error_feedback: accumulate the compression residual and re-inject it
        next round (EF; required for the biased sparsifiers, helps the
        quantizers too).
      seed: PRNG seed for stochastic rounding / random sparsification.
      use_kernel: serve int8 quantize + dequantize-accumulate with the fused
        Pallas kernel instead of the jnp path (TPU, or interpret for tests).
      interpret: run the Pallas kernel in interpret mode (CPU testing).
      block_d: Pallas kernel block length along the flattened param dim.
      gamma: consensus step size for the correction θ += γ(Σ_j W_ij θ̂_j − θ̂_i).
        γ=1 is exact mixing of the public copies and is stable for the
        high-fidelity codecs (bf16/int8/int4); the sparsifiers need γ < 1 or
        the innovation loop diverges (Koloskova et al. 2019, Thm. 2). None
        picks 1.0 for quantizers and min(1, 2·ratio) for topk/randk.
      schedule: optional :class:`~repro.comm.schedule.ScheduleConfig` that
        moves the codec rate during training (int8→int4 / annealed ratio),
        driven by the round counter or the error-feedback innovation norm.
    """

    kind: str = "none"
    ratio: float = 0.01
    error_feedback: bool = True
    seed: int = 0
    use_kernel: bool = False
    interpret: bool = False
    block_d: int = 65536
    gamma: float | None = None
    schedule: ScheduleConfig | None = None

    def __post_init__(self):
        if self.kind not in ("none", "bf16", "int8", "int4", "topk", "randk"):
            raise ValueError(f"unknown compression kind {self.kind!r}")
        if self.kind in ("topk", "randk") and not 0.0 < self.ratio <= 1.0:
            raise ValueError("ratio must be in (0, 1]")
        if self.use_kernel and self.kind != "int8":
            raise ValueError("the fused quant_gossip kernel serves kind='int8'")
        if self.schedule is not None:
            if self.kind not in ("int8", "int4", "topk", "randk"):
                raise ValueError(
                    f"kind {self.kind!r} has no adjustable rate to schedule")
            if self.schedule.kind == "adaptive" and not self.error_feedback:
                raise ValueError(
                    "adaptive schedules are driven by the error-feedback "
                    "innovation norm; set error_feedback=True")

    @property
    def enabled(self) -> bool:
        return self.kind != "none"

    @property
    def resolved_gamma(self) -> float:
        if self.gamma is not None:
            return self.gamma
        if self.kind in ("topk", "randk"):
            return min(1.0, 2.0 * self.ratio)
        return 1.0


@runtime_checkable
class Compressor(Protocol):
    """Per-leaf wire codec. ``x`` is (K, D) float32; payload is a pytree."""

    name: str

    def compress(self, x: jax.Array, keys: jax.Array,
                 rate: jax.Array | None = None) -> Any:
        """Encode ``x`` into the wire payload (what ppermute actually moves).

        ``keys`` is a batch of per-node-row PRNG keys (:func:`per_node_keys`);
        ``rate`` is an optional traced codec rate from a schedule.
        """
        ...

    def decompress(self, payload: Any, d: int) -> jax.Array:
        """Decode a payload back to (K, d) float32."""
        ...

    def payload_bytes(self, d: int) -> int:
        """Static wire bytes *per node* for a leaf of per-node size d, at
        the full (unscheduled) rate."""
        ...

    def payload_bits(self, d: int, rate: jax.Array | None = None):
        """Wire bits per node for per-node size d — traced when ``rate``
        is; equals ``8 * payload_bytes(d)`` at rate None."""
        ...


class NoCompressor:
    name = "none"

    def compress(self, x, keys, rate=None):
        return x

    def decompress(self, payload, d):
        return payload

    def payload_bytes(self, d):
        return 4 * d

    def payload_bits(self, d, rate=None):
        return 8 * self.payload_bytes(d)


class BF16Compressor:
    name = "bf16"

    def compress(self, x, keys, rate=None):
        return x.astype(jnp.bfloat16)

    def decompress(self, payload, d):
        return payload.astype(jnp.float32)

    def payload_bytes(self, d):
        return 2 * d

    def payload_bits(self, d, rate=None):
        return 8 * self.payload_bytes(d)


def _pack_int4(q: jax.Array) -> jax.Array:
    """(K, D) int8 nibbles in [-8, 7] -> (K, ceil(D/2)) packed int8."""
    k, d = q.shape
    if d % 2:
        q = jnp.pad(q, ((0, 0), (0, 1)))
    lo = jnp.bitwise_and(q[:, 0::2], jnp.int8(0x0F))
    hi = jnp.left_shift(q[:, 1::2], 4)
    return jnp.bitwise_or(lo, hi)


def _unpack_int4(packed: jax.Array, d: int) -> jax.Array:
    """Inverse of :func:`_pack_int4` (arithmetic shifts sign-extend)."""
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)
    out = jnp.stack([lo, hi], axis=-1).reshape(packed.shape[0], -1)
    return out[:, :d]


class IntQuantizer:
    """Stochastically rounded uniform quantizer with per-node float32 scale.

    With a traced ``rate`` (the dynamic qmax) the buffer stays (K, D) int8 —
    packing is shape-static — and ``payload_bits`` accounts the effective
    bit-width; the static int4 path nibble-packs for a genuinely halved
    buffer.
    """

    def __init__(self, bits: int, dynamic: bool = False):
        if bits not in (4, 8):
            raise ValueError("bits must be 4 or 8")
        self.bits = bits
        self.qmax = (1 << (bits - 1)) - 1  # 127 / 7
        self.dynamic = dynamic
        self.name = f"int{bits}"

    def _pack(self) -> bool:
        return self.bits == 4 and not self.dynamic

    def _scale(self, x, qmax):
        absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        return jnp.where(absmax > 0, absmax / qmax, 1.0)

    def compress(self, x, keys, rate=None):
        qmax = jnp.float32(self.qmax) if rate is None else rate
        scale = self._scale(x, qmax)
        u = _uniform_rows(keys, x.shape[1])
        q = jnp.clip(jnp.floor(x / scale + u), -qmax, qmax)
        q = q.astype(jnp.int8)
        if self._pack():
            q = _pack_int4(q)
        return q, scale

    def decompress(self, payload, d):
        q, scale = payload
        if self._pack():
            q = _unpack_int4(q, d)
        return q.astype(jnp.float32) * scale

    def value_bytes(self, d):
        """Bytes of the quantized values on the wire (packed nibbles for
        static int4, one byte per entry otherwise)."""
        return d if not self._pack() else (d + 1) // 2

    def payload_bytes(self, d):
        return self.value_bytes(d) + _SCALE_BYTES

    def payload_bits(self, d, rate=None):
        if rate is None:
            return 8 * self.payload_bytes(d)
        return quant_bits(rate) * d + 8 * _SCALE_BYTES


class KernelInt8Quantizer(IntQuantizer):
    """int8 quantizer served by the fused Pallas quant_gossip kernel.

    Same wire format as :class:`IntQuantizer` except the scale is per
    (node, block) and the int8 values keep the kernel's tile view (the
    ragged tail of a leaf rides zero-padded): the kernel computes each
    block's absmax and quantizes it in one VMEM-resident pass, and
    ``accumulate`` fuses dequantize with the weighted neighbor combine so
    the full-precision message never exists.  The dynamic qmax rides into
    the kernel as a traced SMEM scalar, so a schedule's int8→int4 switch
    costs no recompile.
    """

    def __init__(self, block_d: int = 65536, interpret: bool = False,
                 dynamic: bool = False):
        super().__init__(bits=8, dynamic=dynamic)
        self.name = "int8-kernel"
        self.block_d = block_d
        self.interpret = interpret

    def compress(self, x, keys, rate=None):
        return self.compress_masked(x, keys, None, rate)

    def decompress(self, payload, d):
        from repro.kernels.quant_gossip.ops import dequantize_tiles

        q, scale = payload
        return dequantize_tiles(q, scale, d)

    def accumulate(self, acc, payload, weight):
        """acc + weight * dequantize(payload), fused (one pass over q)."""
        return self.accumulate_masked(acc, payload, weight, None)

    def compress_masked(self, x, keys, mask, rate=None):
        """Sender-masked quantize via the fused Pallas kernel: masked rows
        emit a zero payload and zero scales (nothing on the wire), so the EF
        innovation of a fully-faulted node stays unsent and its θ̂ frozen.
        ``mask=None`` and an all-ones mask are bit-identical."""
        from repro.kernels.quant_gossip.ops import quantize_tiles

        qmax = jnp.float32(self.qmax) if rate is None else rate
        u = _uniform_rows(keys, x.shape[1])
        return quantize_tiles(x, u, qmax=qmax, block_d=self.block_d,
                              mask=mask, interpret=self.interpret)

    def accumulate_masked(self, acc, payload, weight, mask):
        """acc + mask·weight·dequantize(payload), fused; masked links add
        exactly 0 (bitwise passthrough of acc)."""
        from repro.kernels.quant_gossip.ops import dequant_accumulate_tiles

        q, scale = payload
        return dequant_accumulate_tiles(acc, q, scale, weight, mask,
                                        interpret=self.interpret)

    def _n_blocks(self, d):
        from repro.kernels.quant_gossip.kernel import num_blocks

        return num_blocks(d, self.block_d)

    def value_bytes(self, d):
        from repro.kernels.quant_gossip.kernel import block_len

        n = self._n_blocks(d)
        return n * block_len(d, n)

    def payload_bytes(self, d):
        return self.value_bytes(d) + _SCALE_BYTES * self._n_blocks(d)

    def payload_bits(self, d, rate=None):
        if rate is None:
            return 8 * self.payload_bytes(d)
        return (quant_bits(rate) * self.value_bytes(d)
                + 8 * _SCALE_BYTES * self._n_blocks(d))


def _num_kept(d: int, ratio: float) -> int:
    return max(1, min(d, int(round(ratio * d))))


class TopKCompressor:
    """Keep the ``ratio`` fraction of largest-magnitude entries per node.

    ``ratio`` sizes the (static) payload buffer; a traced ``rate`` ≤ ratio
    masks the tail of the magnitude-sorted buffer so only ``round(rate·d)``
    entries are live on the wire (``payload_bits`` counts exactly those).
    """

    def __init__(self, ratio: float):
        self.ratio = ratio
        self.name = "topk"

    def _dynamic_kept(self, d, rate):
        kk_max = _num_kept(d, self.ratio)
        return jnp.clip(jnp.round(rate * d), 1, kk_max)

    def _mask_tail(self, vals, d, rate):
        # top_k output is magnitude-sorted, so masking the tail keeps the
        # largest entries (randk: an arbitrary-but-fixed subset, also fine)
        kk_t = self._dynamic_kept(d, rate)
        live = jnp.arange(vals.shape[1], dtype=jnp.float32)[None, :] < kk_t
        return jnp.where(live, vals, 0.0)

    def compress(self, x, keys, rate=None):
        kk = _num_kept(x.shape[1], self.ratio)
        _, idx = jax.lax.top_k(jnp.abs(x), kk)
        vals = jnp.take_along_axis(x, idx, axis=1)
        if rate is not None:
            vals = self._mask_tail(vals, x.shape[1], rate)
        return vals, idx.astype(jnp.int32)

    def decompress(self, payload, d):
        vals, idx = payload
        rows = jnp.arange(vals.shape[0])[:, None]
        return jnp.zeros((vals.shape[0], d), jnp.float32).at[rows, idx].set(vals)

    def payload_bytes(self, d):
        return _num_kept(d, self.ratio) * 8  # f32 value + int32 index

    def payload_bits(self, d, rate=None):
        if rate is None:
            return 8 * self.payload_bytes(d)
        return self._dynamic_kept(d, rate) * 64.0


class RandKCompressor(TopKCompressor):
    """Keep a uniformly random ``ratio`` fraction per node (fresh each round).

    Unscaled (E[ĉ] = ratio·x): pair with error feedback, which re-injects
    what was dropped, rather than the 1/ratio variance-inflating rescale.
    """

    def __init__(self, ratio: float):
        super().__init__(ratio)
        self.name = "randk"

    def compress(self, x, keys, rate=None):
        k, d = x.shape
        kk = _num_kept(d, self.ratio)
        scores = _uniform_rows(keys, d)
        idx = jax.lax.top_k(scores, kk)[1]
        vals = jnp.take_along_axis(x, idx, axis=1)
        if rate is not None:
            vals = self._mask_tail(vals, d, rate)
        return vals, idx.astype(jnp.int32)


def make_compressor(cfg: CompressionConfig) -> Compressor:
    dynamic = cfg.schedule is not None
    if cfg.kind == "none":
        return NoCompressor()
    if cfg.kind == "bf16":
        return BF16Compressor()
    if cfg.kind in ("int8", "int4"):
        if cfg.use_kernel:
            return KernelInt8Quantizer(cfg.block_d, cfg.interpret,
                                       dynamic=dynamic)
        # scheduled quantizers share the int8 container (packing is
        # shape-static); the schedule drives the effective bit-width
        return IntQuantizer(8 if dynamic else int(cfg.kind[3:]),
                            dynamic=dynamic)
    if cfg.kind == "topk":
        return TopKCompressor(cfg.ratio)
    if cfg.kind == "randk":
        return RandKCompressor(cfg.ratio)
    raise ValueError(cfg.kind)
