"""ComposedMixer: Topology × Transport × Wire behind the v2 Mixer protocol.

The consensus matrix used to be nine classes glued by multiple inheritance
({Dense, Gossip, Hierarchical} × {static, Dynamic} × {plain, Compressed}).
It is now ONE operator assembled from three orthogonal layers:

* **Topology** (``comm/topology.py``) — who talks to whom this round:
  ``round_w(rounds)``, static / scheduled∘faults / star.
* **Transport** (``comm/transport.py``) — how payloads move: dense einsum,
  shard_map+ppermute gossip (± hierarchical replica psum), star/hub mean.
* **Wire** (``comm/wire.py``) — what crosses each link: identity,
  memoryless codec, CHOCO error feedback (± delta/re-base clock), masked
  int8/int4 Pallas — each owning exactly the ``CommState`` fields it
  declares.

The legacy class names survive as thin constructor shims assembling layer
stacks (``DenseMixer = Static × Dense × Identity``, ...), which keeps
``obs:consensus/<name>`` scopes, isinstance relationships, and constructor
signatures intact; every shipped stack is bit-exact against its
pre-refactor trajectory (``tests/data/mixer_anchors.json`` gates all 22).

Round bodies (the traced code below) are the frozen pre-refactor paths:

==========================  ==============================================
stack                       round body
==========================  ==============================================
identity × static           base ``Mixer.__call__`` over :meth:`_mix`
identity × scheduled×dense  :meth:`_dynamic_dense_call` (traced W einsum,
                            active-link wire accounting)
identity × scheduled×gossip :meth:`_dynamic_gossip_call` (gathered
                            per-round vectors, plain or masked-quant wire)
codec × dense               :meth:`_dense_round` (static or traced W)
codec × gossip (static)     :meth:`_gossip_round` (no overrides)
choco+clock × sched×gossip  :meth:`_clocked_gossip_call` (delta/re-base
                            two-mode ``lax.cond`` on ``ef_rounds``)
==========================  ==============================================

Sanitizer duck-typing contract (``repro.analysis.sanitize``): the
*instance* attributes ``_round_topology_w`` (time-varying stacks only) and
``_round_vectors`` (dynamic gossip with identity/masked wires only) are
assigned per-stack in ``__init__`` — ``hasattr`` gating must match the
legacy classes exactly, or the sanitized program changes shape.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.compressors import fold_leaf, per_node_keys
from repro.comm.protocol import (
    CommState,
    Mixer,
    trivial_comm_state,
    trivial_state_specs,
)
from repro.comm.topology import (
    StarTopology,
    Topology,
    active_links,
    active_sends,
    gather_round_vectors,
)
from repro.comm.transport import (
    DenseTransport,
    GossipTransport,
    StarTransport,
    Transport,
    gossip_mix_local,
)
from repro.comm.wire import (
    ChocoWire,
    CodecWire,
    MaskedQuantWire,
    Wire,
    _codec_wire_dtypes,
    _leaf_payload_bytes,
    _merge_dtype_bytes,
    _send_mask,
)
from repro.utils.tree import tree_bytes


class ComposedMixer(Mixer):
    """One consensus operator over a (topology, transport, wire) stack.

    ``topology=None`` + ``transport=None`` is the no-communication stack
    (IdentityMixer); ``topology=None`` with a gossip transport is the
    static-gossip stack (the W lives frozen in the decomposition weights).
    Legacy attribute surface (``.k``, ``.w``, ``.gamma``, ``.topology`` =
    the TopologySchedule, ``.faults``, ``.perms``, ...) is mirrored from
    the layers at construction so external duck-typing (sanitize, audit,
    benchmarks, tests) keeps working unchanged.
    """

    def __init__(self, topology: Topology | None,
                 transport: Transport | None, wire: Wire):
        self.topo = topology
        self.transport = transport
        self.wire = wire
        dynamic = topology is not None and topology.time_varying
        self._dynamic = dynamic
        self._is_gossip = isinstance(transport, GossipTransport)

        if topology is not None:
            self.k = topology.k
        elif transport is not None:
            self.k = transport.k

        if dynamic:
            # legacy names: .topology is the TopologySchedule (tests mutate
            # it), .faults the enabled FaultConfig or None; the sanitizer
            # duck-types on the hasattr of _round_topology_w (instance
            # attribute — static stacks must NOT grow it)
            self.topology = topology.schedule
            self.faults = topology.faults
            self._round_topology_w = topology.round_w

        if isinstance(transport, DenseTransport):
            self.compute_dtype = transport.compute_dtype
            if not dynamic:
                # DenseMixer's historical construction-time cast: the
                # static W is materialized once at compute_dtype
                self.w = jnp.asarray(topology.base_weights(),
                                     transport.compute_dtype)
        elif isinstance(transport, StarTransport):
            self.w = jnp.asarray(topology.base_weights(), jnp.float32)
        elif self._is_gossip:
            t = transport
            self.mesh = t.mesh
            self.axis = t.axis
            self.param_specs = t.param_specs
            self.perms = t.perms
            self.replica_axis = t.replica_axis
            self.self_w = t.self_w
            self.match_ws = t.match_ws
            self.decomp = t.decomp
            self._p_node = t._p_node
            self._perm_idx = t._perm_idx
            if dynamic and not isinstance(wire, CodecWire):
                # sanitize's mask-binariness check keys off this hasattr;
                # the clocked EF stack deliberately does not expose it
                self._round_vectors = partial(gather_round_vectors,
                                              perm_idx=t._perm_idx)
            if topology is not None and dynamic and topology.k != t.k:
                raise ValueError(
                    f"topology K={topology.k} != transport K={t.k}")

        if isinstance(wire, CodecWire):
            if transport is None:
                raise ValueError("a codec wire needs a transport")
            if isinstance(transport, StarTransport):
                raise ValueError(
                    "codec wires on the hub stack ride the dense transport "
                    "with the star W (see make_hub_mixer)")
            self.compressor = wire.compressor
            self.gamma = wire.gamma
            self.ef = wire.ef
            self.schedule = wire.schedule
            clock = getattr(wire, "clock", None)
            if clock is not None:
                if not (self._is_gossip and dynamic):
                    raise ValueError(
                        "the delta/re-base clock serves the dynamic gossip "
                        "stack (incremental hat_mix cache); dense re-mixes "
                        "the full public-copy matrix every round")
                self.adaptive = clock.adaptive
                self.ef_rebase_every = int(clock.every)
                self.ef_rebase_threshold = float(clock.threshold)
        elif isinstance(wire, MaskedQuantWire):
            if not (self._is_gossip and dynamic):
                raise ValueError(
                    "the masked quant wire rides the dynamic gossip "
                    "transport (per-round link masks)")
            self.quantized = wire.quantized
            self._qmax = wire._qmax
            self._compressor = wire.compressor
        if isinstance(transport, StarTransport) and dynamic:
            raise ValueError(
                "the hub stack has no fault/schedule model yet — "
                "the star topology is static (ROADMAP: federated faults)")

    # -- layer delegation (legacy method surface) ------------------------------

    @property
    def compression(self):
        return self.wire.compression

    @property
    def traced_wire(self) -> bool:
        if self._dynamic:
            return True
        return bool(self.wire.traced_wire)

    def _rate(self, state: CommState):
        """Traced codec rate for the round about to run (None = static) —
        also the sanitizer's rate-in-container hook."""
        return self.wire.rate(state)

    def _next_sched_state(self, state: CommState, res_norm):
        return self.wire.next_sched_state(state, res_norm)

    def _round_wire_bits(self, params, rate, senders):
        return self.wire.round_wire_bits(params, rate, senders, self.k)

    def _encode_leaf(self, x, hat, keys, rate, send_mask=None):
        return self.wire.encode_leaf(x, hat, keys, rate, send_mask=send_mask)

    def _node_index(self):
        return self.transport.node_index()

    def _round_w(self, state: CommState):
        """The mixing matrix of the codec-dense round about to run: static
        W, or the schedule's traced per-round matrix — EF composes with a
        moving W exactly on this lowering because it re-mixes the full
        public-copy matrix every round (no incremental cache to go stale).
        """
        if self._dynamic:
            return self.topo.round_w(state.rounds)
        return self.w

    def _senders(self, w):
        """Wire-accounting sender count: every node injects once on the
        static dense broadcast model; dynamic stacks count active directed
        links out of the traced W (a straggler round bills 0)."""
        if self._dynamic:
            return active_links(w)
        return self.k

    # -- state ----------------------------------------------------------------

    def init_state(self, params) -> CommState:
        fields = self.wire.init_fields(
            params, incremental=self.transport is not None
            and self.transport.incremental)
        state = trivial_comm_state()
        return state._replace(**fields) if fields else state

    def state_specs(self, param_specs) -> CommState:
        fields = self.wire.spec_fields(
            param_specs, incremental=self.transport is not None
            and self.transport.incremental)
        specs = trivial_state_specs()
        return specs._replace(**fields) if fields else specs

    # -- accounting ------------------------------------------------------------

    def bytes_per_round(self, params) -> int:
        """Static estimate of wire bytes one consensus round injects (the
        traced ``CommState.wire_bits`` is authoritative for dynamic and
        scheduled stacks)."""
        t = self.transport
        if t is None:
            return 0
        if isinstance(self.wire, MaskedQuantWire):
            sends = sum(len(pairs) for pairs in self.perms)
            per_node = sum(self.wire.leaf_bits(x.size // self.k)
                           for x in jax.tree.leaves(params)) / 8.0
            return round(sends * per_node)
        if isinstance(self.wire, CodecWire):
            q = _leaf_payload_bytes(self.compressor, params, self.k)
            if not self._is_gossip:
                # dense codec: every node injects its payload once
                return self.k * q
            sends = sum(len(pairs) for pairs in self.perms)
            clock = getattr(self.wire, "clock", None)
            if clock is None:
                return sends * q
            # clocked EF: fault-free amortized estimate over the FULL union
            # support — ((B−1)·compressed + 1·f32 re-base)/B per link
            full = 4 * sum(x.size // self.k
                           for x in jax.tree.leaves(params))
            if clock.adaptive:
                b = max(clock.every, 1)
                return round(sends * ((b - 1) * q + full) / b)
            b = clock.every
            if b == 0:
                return sends * q
            if b == 1:
                return sends * full
            return round(sends * ((b - 1) * q + full) / b)
        # identity wire
        if isinstance(t, StarTransport):
            # hub round: K uploads + K downloads of the per-node block
            return 2 * tree_bytes(params)
        if isinstance(t, DenseTransport):
            if self._dynamic:
                try:
                    base = np.asarray(self.topo.base_weights())
                    sends = int(np.count_nonzero(base) - self.k)
                except ValueError:  # moving support: assume complete
                    sends = self.k * (self.k - 1)
                return sends * tree_bytes(params) // self.k
            # uncompressed static dense: every node injects its block once
            return tree_bytes(params)
        sends = sum(len(pairs) for pairs in self.perms)
        return sends * tree_bytes(params) // self.k

    def wire_dtype_bytes(self, params) -> dict[str, float] | None:
        """Physical per-HLO-dtype collective bytes of ONE compiled round
        (None for the einsum/star simulations, which emit no collectives —
        the ``audit_wire`` contract)."""
        if not self._is_gossip:
            return None
        sends = sum(len(pairs) for pairs in self.perms)
        if isinstance(self.wire, MaskedQuantWire):
            # the masked wire always moves the full union-support buffers,
            # and the int4 rate rides the int8 *container*: per-entry
            # container bytes, deliberately larger than the effective-bit
            # bytes_per_round accounting
            out: dict[str, float] = {}
            for x in jax.tree.leaves(params):
                d = x.size // self.k
                out["s8"] = out.get("s8", 0.0) + sends * d
                out["f32"] = out.get("f32", 0.0) \
                    + sends * 4.0 * self._compressor._n_blocks(d)
            return out
        if isinstance(self.wire, CodecWire):
            delta = _merge_dtype_bytes(*[
                _codec_wire_dtypes(self.compressor, x.size // self.k)
                for x in jax.tree.leaves(params)], scale=sends)
            clock = getattr(self.wire, "clock", None)
            if clock is None:
                return delta
            # both lax.cond modes live in the program when both can run:
            # delta moves the codec payload, re-base the f32 public copies
            full = {"f32": 4.0 * sends * sum(x.size // self.k
                                             for x in jax.tree.leaves(params))}
            if clock.adaptive or clock.every >= 2:
                return _merge_dtype_bytes(delta, full)
            if clock.every == 0:
                return delta
            return full
        from repro.utils.hlo import hlo_dtype_name

        out = {}
        for x in jax.tree.leaves(params):
            dt = hlo_dtype_name(x.dtype)
            out[dt] = out.get(dt, 0.0) \
                + sends * (x.size // self.k) * x.dtype.itemsize
        return out

    # -- pure application (identity-wire bodies) -------------------------------

    def _mix(self, theta):
        t = self.transport
        if t is None:
            return theta
        if isinstance(t, StarTransport):
            return t.apply(theta)
        if isinstance(t, DenseTransport):
            return t.apply_w(self.w, theta)
        return self._plain_gossip(theta, self.self_w, self.match_ws)

    def _plain_gossip(self, theta, self_w, match_ws):
        t = self.transport
        inner = partial(gossip_mix_local, axis=t.axis, perms=t.perms)
        if t.replica_axis is not None:
            r = t.mesh.shape[t.replica_axis]

            def body(tr, sw, mws):
                # average the within-node replicas (plain DP all-reduce
                # over ICI), then the per-node consensus over the node axis
                tr = jax.tree.map(
                    lambda x: jax.lax.psum(x, t.replica_axis) / r, tr)
                return inner(tr, sw, mws)
        else:
            def body(tr, sw, mws):
                return inner(tr, sw, mws)

        return jax.shard_map(
            body,
            mesh=t.mesh,
            in_specs=(t.param_specs, t._p_node,
                      [t._p_node] * len(match_ws)),
            out_specs=t.param_specs,
        )(theta, self_w, list(match_ws))

    def mix_tree(self, tree, state: CommState):
        """Pure consensus application to an arbitrary pytree with this
        round's topology (no state advance, no codec) — the
        gradient-tracking tracker exchange.  Codec wires do not implement
        this (their wire is entangled with their state)."""
        if isinstance(self.wire, CodecWire):
            raise NotImplementedError
        if self._dynamic:
            w = self.topo.round_w(state.rounds)
            if isinstance(self.transport, DenseTransport):
                return self.transport.apply_w(w, tree)
            self_w, match_ws, _ = gather_round_vectors(w, self._perm_idx)
            return self._plain_gossip(tree, self_w, match_ws)
        return self._mix(tree)

    # -- the protocol ----------------------------------------------------------

    def __call__(self, theta, state: CommState, *, round=None):
        if isinstance(self.wire, CodecWire):
            if self._is_gossip and getattr(self.wire, "clock", None) is not None:
                return self._clocked_gossip_call(theta, state)
            with jax.named_scope(f"obs:consensus/{type(self).__name__}"):
                if self._is_gossip:
                    return self._gossip_round(theta, state)
                return self._dense_round(theta, state)
        if self._dynamic:
            if self._is_gossip:
                return self._dynamic_gossip_call(theta, state)
            return self._dynamic_dense_call(theta, state)
        return super().__call__(theta, state, round=round)

    # -- identity-wire dynamic rounds ------------------------------------------

    def _dynamic_dense_call(self, theta, state: CommState):
        with jax.named_scope(f"obs:consensus/{type(self).__name__}"):
            w = self.topo.round_w(state.rounds)
            mixed = self.transport.apply_w(w, theta)
        per_node_bits = 8.0 * (tree_bytes(theta) // self.k)
        return mixed, state._replace(
            rounds=state.rounds + 1,
            wire_bits=active_links(w) * per_node_bits,
        )

    def _dynamic_gossip_call(self, theta, state: CommState):
        quantized = getattr(self, "quantized", None)
        with jax.named_scope(f"obs:consensus/{type(self).__name__}"):
            w = self.topo.round_w(state.rounds)
            self_w, match_ws, masks = gather_round_vectors(w, self._perm_idx)
            key = state.key
            if quantized is None:
                mixed = self._plain_gossip(theta, self_w, match_ws)
                per_node_bits = 8.0 * (tree_bytes(theta) // self.k)
            else:
                key, sub = jax.random.split(state.key)
                mixed = self._quantized_gossip(theta, self_w, match_ws,
                                               masks, sub)
                # shape-only host math (.size / .k are python ints): no
                # tracer is materialized
                per_node_bits = float(sum(  # repro: noqa[RPR002]
                    self.wire.leaf_bits(x.size // self.k)
                    for x in jax.tree.leaves(theta)))
        sends = sum(jnp.sum(m) for m in masks)
        return mixed, state._replace(
            key=key,
            rounds=state.rounds + 1,
            wire_bits=jnp.asarray(sends * per_node_bits, jnp.float32),
        )

    def _quantized_gossip(self, theta, self_w, match_ws, masks, key):
        from repro.kernels.quant_gossip.ops import quant_gossip_round

        t = self.transport
        cfg = self.quantized

        def body(tr, sw, mws, mks, k0):
            leaves, treedef = jax.tree.flatten(tr)
            out = []
            for i, x in enumerate(leaves):
                k_local = x.shape[0]
                d = x.size // k_local
                xf = x.reshape(k_local, d).astype(jnp.float32)
                acc = xf * sw[:, None]
                lk = jax.random.fold_in(
                    jax.random.fold_in(k0, i), self._node_index())
                for m, (pw, mk, perm) in enumerate(
                        zip(mws, mks, t.perms)):
                    acc = quant_gossip_round(
                        xf, acc, pw, t.axis, perm,
                        jax.random.fold_in(lk, m), mask=mk, qmax=self._qmax,
                        block_d=cfg.block_d, interpret=cfg.interpret,
                        use_kernel=cfg.use_kernel)
                out.append(acc.reshape(x.shape).astype(x.dtype))
            return treedef.unflatten(out)

        p_rep = jax.sharding.PartitionSpec()
        n = len(t.perms)
        return jax.shard_map(
            body,
            mesh=t.mesh,
            check_vma=False,
            in_specs=(t.param_specs, t._p_node,
                      [t._p_node] * n, [t._p_node] * n, p_rep),
            out_specs=t.param_specs,
        )(theta, self_w, list(match_ws), list(masks), key)

    # -- codec-wire rounds -----------------------------------------------------

    def _dense_round(self, theta, state: CommState):
        w = self._round_w(state)
        key, sub = jax.random.split(state.key)
        rate = self._rate(state)
        gamma = self.wire.gamma_for(rate)
        node_ks = per_node_keys(sub, jnp.arange(self.k))
        # a time-varying W can isolate a node: like the gossip lowering's
        # sender mask, it then sends nothing and its θ̂ stays frozen
        send = None
        if self._dynamic:
            off_diag = w * (1.0 - jnp.eye(self.k, dtype=w.dtype))
            send = jnp.any(off_diag != 0, axis=1).astype(jnp.float32)
        leaves, treedef = jax.tree.flatten(theta)
        hats = (treedef.flatten_up_to(state.hat) if self.ef
                else [() for _ in leaves])
        out_theta, out_hat = [], []
        res_sq = jnp.float32(0.0)
        for i, (x, h) in enumerate(zip(leaves, hats)):
            k = x.shape[0]
            xf = x.reshape(k, -1).astype(jnp.float32)
            hf = h.reshape(k, -1) if self.ef else None
            if self.ef:
                res_sq = res_sq + jnp.sum(jnp.square(xf - hf))
            _, public, new_hat = self._encode_leaf(
                xf, hf, fold_leaf(node_ks, i), rate, send_mask=send)
            mixed = jnp.einsum(
                "kl,ld->kd", w, public,
                precision=jax.lax.Precision.HIGHEST)
            out = xf + gamma * (mixed - public)
            out_theta.append(out.reshape(x.shape).astype(x.dtype))
            if self.ef:
                out_hat.append(new_hat.reshape(x.shape))
        res_norm, res_ref, rounds = self._next_sched_state(
            state, jnp.sqrt(res_sq))
        unflat = treedef.unflatten
        # _replace, not CommState(...): fields this round does not own
        # (track, ef_rounds, ef_drift, ...) must thread through untouched —
        # an explicit construction silently resets any field added later
        # (the PR-4/PR-5 bug class; repro.analysis lint RPR005 enforces it)
        return unflat(out_theta), state._replace(
            hat=unflat(out_hat) if self.ef else (), key=key,
            res_norm=res_norm, res_ref=res_ref, rounds=rounds,
            wire_bits=self._round_wire_bits(theta, rate,
                                            senders=self._senders(w)))

    def _gossip_round(self, theta, state: CommState, *, self_w=None,
                      match_ws=None, masks=None, senders=None):
        """One compressed gossip round over the matching decomposition.

        The static stack calls this with no overrides (frozen decomposition
        weights, every matching link active).  The clocked dynamic stack
        passes the *traced* per-round vectors gathered from W_r: ``self_w``
        (K,), ``match_ws``/``masks`` per matching, and the traced
        active-link count ``senders`` for wire accounting.  With all-ones
        masks the masked paths are bit-identical to the unmasked ones,
        which is what makes the static-schedule anchor exact.
        """
        t = self.transport
        key, sub = jax.random.split(state.key)
        rate = self._rate(state)
        p_node = jax.sharding.PartitionSpec(t.axis)
        p_rep = jax.sharding.PartitionSpec()
        specs = t.param_specs
        ef = self.ef
        have_rate = rate is not None
        have_masks = masks is not None
        if self_w is None:
            self_w = t.self_w
        match_ws = list(t.match_ws) if match_ws is None else list(match_ws)
        mask_args = list(masks) if have_masks else []

        def body(tr, hat, s, self_w, match_ws, mks, k0, rate_op):
            r_op = rate_op if have_rate else None
            gam = self.wire.gamma_for(r_op)
            send = _send_mask(mks) if have_masks else None
            leaves, treedef = jax.tree.flatten(tr)
            k_local = leaves[0].shape[0] if leaves else 1
            # global node ids of the local rows -> dense-identical keys
            rows = self._node_index() * k_local + jnp.arange(k_local)
            node_ks = per_node_keys(k0, rows)
            hats = (treedef.flatten_up_to(hat) if ef
                    else [() for _ in leaves])
            mixes = (treedef.flatten_up_to(s) if ef
                     else [() for _ in leaves])
            o_t, o_h, o_s = [], [], []
            res_sq = jnp.float32(0.0)
            for i, (x, h, sm) in enumerate(zip(leaves, hats, mixes)):
                k_local = x.shape[0]
                d = x.size // k_local
                xf = x.reshape(k_local, d).astype(jnp.float32)
                if t.replica_axis is not None:
                    r = t.mesh.shape[t.replica_axis]
                    xf = jax.lax.psum(xf, t.replica_axis) / r
                if ef:
                    res_sq = res_sq + jnp.sum(
                        jnp.square(xf - h.reshape(k_local, d)))
                payload, public, new_hat = self._encode_leaf(
                    xf, h.reshape(k_local, d) if ef else None,
                    fold_leaf(node_ks, i), r_op, send_mask=send)
                # EF: s_i += W_ii q_i + Σ_m W_i,perm(i)·dequant(recv) keeps
                # s_i = Σ_j W_ij θ̂_j current; memoryless: same combine of the
                # fresh C(θ) messages.  Only the payload crosses the wire.
                base = sm.reshape(k_local, d) if ef else jnp.zeros_like(xf)
                delta_or_msg = (public - h.reshape(k_local, d)) if ef else public
                acc = base + self_w[:, None] * delta_or_msg
                for m, (pw, perm) in enumerate(zip(match_ws, t.perms)):
                    recv = jax.tree.map(
                        lambda leaf: jax.lax.ppermute(leaf, t.axis, perm),
                        payload)
                    acc = self._accumulate(acc, recv, pw[:, None], d,
                                           mask=mks[m] if have_masks else None)
                out = xf + gam * (acc - public)
                o_t.append(out.reshape(x.shape).astype(x.dtype))
                if ef:
                    o_h.append(new_hat.reshape(x.shape))
                    o_s.append(acc.reshape(x.shape))
            res_sq = jax.lax.psum(res_sq, t.axis)
            u = treedef.unflatten
            return (u(o_t), u(o_h) if ef else (), u(o_s) if ef else (),
                    res_sq)

        in_hat = (specs if ef else (), specs if ef else ())
        shard = jax.shard_map(
            body,
            mesh=t.mesh,
            check_vma=False,
            in_specs=(specs, in_hat[0], in_hat[1], p_node,
                      [p_node] * len(match_ws), [p_node] * len(mask_args),
                      p_rep, p_rep),
            out_specs=(specs, in_hat[0], in_hat[1], p_rep),
        )
        rate_op = rate if have_rate else jnp.float32(0.0)
        t2, h2, s2, res_sq = shard(theta, state.hat, state.hat_mix,
                                   self_w, match_ws, mask_args, sub,
                                   rate_op)
        res_norm, res_ref, rounds = self._next_sched_state(
            state, jnp.sqrt(res_sq))
        if senders is None:
            senders = sum(len(pairs) for pairs in t.perms)
        # _replace so fields this round does not own thread through (RPR005)
        return t2, state._replace(
            hat=h2, hat_mix=s2, key=key,
            res_norm=res_norm, res_ref=res_ref, rounds=rounds,
            wire_bits=self._round_wire_bits(theta, rate, senders=senders))

    def _accumulate(self, acc, payload, weight, d, mask=None):
        """acc + weight·dequant(payload), with an optional traced link mask.

        ``mask`` (K_local,) in {0, 1}: masked links must contribute exactly
        acc — the dynamic stacks gather per-round weights out of W_r, so a
        dropped link already has weight 0, and the mask makes the
        passthrough bitwise (and lets a mask-consulting transport skip the
        payload entirely).  ``mask=None``/all-ones are bit-identical.
        """
        if mask is None:
            fused = getattr(self.compressor, "accumulate", None)
            if fused is not None:
                return fused(acc, payload, weight)
            return acc + weight * self.compressor.decompress(payload, d)
        fused = getattr(self.compressor, "accumulate_masked", None)
        if fused is not None:
            return fused(acc, payload, weight, mask)
        return acc + (weight * mask[:, None]) * self.compressor.decompress(
            payload, d)

    # -- the clocked EF gossip stack (delta / re-base two-mode) ----------------

    def _cache_drift(self, w, hat, hat_mix):
        """‖s − W θ̂‖_F over all leaves: the exact staleness of the
        incremental cache under the round's topology — the drift proxy the
        adaptive re-base triggers on (mirroring how the codec schedule keys
        off ``res_norm``).  A (K, K) einsum against the node-stacked public
        copies; only computed in adaptive mode."""
        total = jnp.float32(0.0)
        for h, s in zip(jax.tree.leaves(hat), jax.tree.leaves(hat_mix)):
            hf = h.reshape(self.k, -1)
            sf = s.reshape(self.k, -1)
            ws = jnp.einsum("kl,ld->kd", w, hf,
                            precision=jax.lax.Precision.HIGHEST)
            total = total + jnp.sum(jnp.square(sf - ws))
        return jnp.sqrt(total)

    def _clocked_gossip_call(self, theta, state: CommState):
        with jax.named_scope(f"obs:consensus/{type(self).__name__}"):
            w = self.topo.round_w(state.rounds)
            self_w, match_ws, masks = gather_round_vectors(w, self._perm_idx)
            senders = active_sends(masks)

            def delta(tr, st):
                return self._gossip_round(tr, st, self_w=self_w,
                                          match_ws=match_ws, masks=masks,
                                          senders=senders)

            def rebase(tr, st):
                return self._rebase_round(tr, st, self_w, match_ws, masks,
                                          senders)

            if self.adaptive:
                # drift-triggered re-base: measure the cache staleness
                # against THIS round's W before mixing and re-base this
                # round when it exceeds the threshold.  Both modes live in
                # one lax.cond program — the trigger is a traced operand,
                # so a threshold sweep never recompiles.
                drift = self._cache_drift(w, state.hat, state.hat_mix)
                t2, s2 = jax.lax.cond(drift > self.ef_rebase_threshold,
                                      rebase, delta, theta, state)
                s2 = s2._replace(ef_drift=drift)
            else:
                b = self.ef_rebase_every
                if b == 0:
                    t2, s2 = delta(theta, state)
                elif b == 1:
                    t2, s2 = rebase(theta, state)
                else:
                    t2, s2 = jax.lax.cond(state.ef_rounds % b == b - 1,
                                          rebase, delta, theta, state)
        return t2, s2._replace(ef_rounds=state.ef_rounds + 1)

    def _rebase_round(self, theta, state: CommState, self_w, match_ws,
                      masks, senders):
        """Codec step + full-precision θ̂ exchange rebuilding the cache.

        The innovation is still encoded (θ̂ must keep tracking θ; masked
        senders stay frozen) but the quantized payload never crosses the
        wire this round — the matchings ppermute the fresh public copies
        instead, and s_i = Σ_j W_ij(r) θ̂_j is exact under the current W.
        """
        t = self.transport
        key, sub = jax.random.split(state.key)
        rate = self._rate(state)
        p_node = jax.sharding.PartitionSpec(t.axis)
        p_rep = jax.sharding.PartitionSpec()
        specs = t.param_specs
        have_rate = rate is not None

        def body(tr, hat, self_w, match_ws, mks, k0, rate_op):
            r_op = rate_op if have_rate else None
            gam = self.wire.gamma_for(r_op)
            send = _send_mask(mks)
            leaves, treedef = jax.tree.flatten(tr)
            k_local = leaves[0].shape[0] if leaves else 1
            rows = self._node_index() * k_local + jnp.arange(k_local)
            node_ks = per_node_keys(k0, rows)
            hats = treedef.flatten_up_to(hat)
            o_t, o_h, o_s = [], [], []
            res_sq = jnp.float32(0.0)
            for i, (x, h) in enumerate(zip(leaves, hats)):
                k_local = x.shape[0]
                d = x.size // k_local
                xf = x.reshape(k_local, d).astype(jnp.float32)
                if t.replica_axis is not None:
                    r = t.mesh.shape[t.replica_axis]
                    xf = jax.lax.psum(xf, t.replica_axis) / r
                hf = h.reshape(k_local, d)
                res_sq = res_sq + jnp.sum(jnp.square(xf - hf))
                _, _, new_hat = self._encode_leaf(
                    xf, hf, fold_leaf(node_ks, i), r_op, send_mask=send)
                acc = self_w[:, None] * new_hat
                for pw, mk, perm in zip(match_ws, mks, t.perms):
                    recv = jax.lax.ppermute(new_hat, t.axis, perm)
                    acc = acc + (pw * mk)[:, None] * recv
                out = xf + gam * (acc - new_hat)
                o_t.append(out.reshape(x.shape).astype(x.dtype))
                o_h.append(new_hat.reshape(x.shape))
                o_s.append(acc.reshape(x.shape))
            res_sq = jax.lax.psum(res_sq, t.axis)
            u = treedef.unflatten
            return u(o_t), u(o_h), u(o_s), res_sq

        n = len(t.perms)
        shard = jax.shard_map(
            body,
            mesh=t.mesh,
            check_vma=False,
            in_specs=(specs, specs, p_node, [p_node] * n, [p_node] * n,
                      p_rep, p_rep),
            out_specs=(specs, specs, specs, p_rep),
        )
        rate_op = rate if have_rate else jnp.float32(0.0)
        t2, h2, s2, res_sq = shard(theta, state.hat, self_w, list(match_ws),
                                   list(masks), sub, rate_op)
        res_norm, res_ref, rounds = self._next_sched_state(
            state, jnp.sqrt(res_sq))
        # full-precision wire: active links × per-node f32 payload
        full_bits = 32.0 * sum(x.size // self.k
                               for x in jax.tree.leaves(theta))
        # _replace so fields this round does not own thread through (RPR005)
        return t2, state._replace(
            hat=h2, hat_mix=s2, key=key,
            res_norm=res_norm, res_ref=res_ref, rounds=rounds,
            wire_bits=jnp.asarray(senders * full_bits, jnp.float32))
