"""Continuous-batching decode engine over a paged (optionally int8) KV pool.

One compiled decode step serves an entire open-loop trace.  The batch is a
fixed shape of ``max_batch`` slots; everything that changes as requests
arrive, finish, or hit EOS is a *traced operand* of that one program:

  ====================  =========  ==============================================
  operand               shape      role
  ====================  =========  ==============================================
  ``tok``               (B, 1)     each slot's last token (next input)
  ``pos``               (B,)       per-slot decode position
  ``active``            (B,)       slot occupancy mask (gates sampling + finish)
  ``limit``             (B,)       last position a slot may decode (budget)
  ``temperature``       (B,)       per-slot sampling temperature (0 = greedy)
  ``tables[kind]``      (B, NB)    block tables into the shared page pools
  ``step``              ()         fold_in index for the sampling PRNG stream
  ====================  =========  ==============================================

The carry (cache + all per-slot operands) lives on the device and the step
advances it in-jit; the host loop's per-step traffic is exactly one (2, B)
int32 readback (sampled tokens + next-active mask).  Slot state is written
from the host only on the rare transitions — admission sets a slot's rows,
eviction points its table row back at the trash page.  Admission runs one
jitted prefill-and-scatter program per distinct prompt length (traffic
classes have fixed prompt lengths, so the set is small and known); a
:class:`repro.obs.RecompileWatchdog` asserts both budgets.

Slot/page lifecycle: admission reserves the request's worst-case page count
from the per-kind free lists and writes its block-table row; eviction (EOS
or budget, decided *inside* the jit via the active mask) frees pages purely
host-side — no device reshape, the freed pages are simply handed to the
next admission, whose prefill overwrites them.  Inactive slots keep
decoding into the trash page (page 0) — masked, never read — which is what
keeps the program shape-stable at any occupancy.

Observability: the engine always owns a :class:`repro.obs.MetricsSink`
(in-memory unless one with a ``log_dir`` is passed) and emits the request
lifecycle as ``trace`` records — ``queued`` → ``admitted`` → ``prefill`` →
``first_token`` → ``finished`` — from these host-side transition paths,
with slot ids, page reservations and run-relative timestamps.  The
``finished`` record carries the request's full latency accounting
(``queued_s``/``ttft_s``/``per_token_s``), making the engine the single
source of latency truth: :func:`repro.obs.report.serve_latency_summary`
derives the bench and CLI summaries from these records.  The compiled
decode step is untouched — zero device callbacks.

Weights: the engine holds its own parameter tree (:func:`serve_weights`).
On a TPU at the default matmul precision every weight that only enters a
dot (attention's ``wq``/``wk``/``wv``/``wo``, the GLU MLPs, the head's
table) is stored in bfloat16, rounded once here: a default-precision
float32 dot rounds it so on every call anyway, and
:func:`repro.models.layers.weight_einsum` reads it as stored.  Elsewhere
(the CPU, ``highest``) the tree is the caller's float32 one.  A tree
assigned to ``engine.params`` later is held the same way, so the compiled
programs see the dtypes they were built for.

Host spans (:func:`repro.obs.host_scope`, read back with
:func:`repro.obs.spans`) time the layers of the host loop, and carry its
counters as attributes:

  ========================  ==================================================
  span                      what it covers (attributes)
  ========================  ==================================================
  ``obs:serve/run``         one :meth:`ServeEngine.run` (clock, requests,
                            weight_bytes, narrow_weight_bytes)
  ``obs:serve/step``        one decode step, a ``StepTraceAnnotation`` (step,
                            active, kv_live_tokens, kv_gathered_tokens)
  ``obs:serve/dispatch``    the decode program's call
  ``obs:serve/readback``    the (2, B) readback, the step's host sync
  ``obs:serve/emit``        per-slot appends, lifecycle records, releases
  ``obs:serve/admit``       one admission (rid, slot, prompt_tokens, pages,
                            scan_tokens, scan_kernel_tokens)
  ``obs:serve/slot_write``  block-table rows and the carry's slot writes
  ``obs:serve/admit_call``  the prefill program's call (or the slot clear)
  ``obs:serve/admit_wait``  the wait for the prefill
  ========================  ==================================================

``scan_tokens`` is the prompt tokens the prefill scans one at a time, times
the recurrent (mamba, rwkv) layers that scan them: 0 for a model of
attention layers alone.  ``scan_kernel_tokens`` is the prompt tokens times
the Mamba layers when the prefill program, as it was traced, ran its Mamba
scans through the ``mamba_scan`` kernel
(:func:`repro.kernels.mamba_scan.ops.traced_paths`), else 0: on the
reference path, the CPU's, and for a one-token prompt.  Inside the prefill
and decode programs, ``obs:ssm/conv`` and ``obs:ssm/scan`` (in
:mod:`repro.models.ssm`) scope the Mamba layers' causal convolution and
selective scan.

``kv_gathered_tokens`` is what the step program reads of the pools,
``max_batch`` times the ring length summed over the paged kinds;
``kv_live_tokens`` is the live part of it: over the slots active when the
step is dispatched and over the paged kinds, the slot's context (``s0`` plus
the tokens emitted so far) capped at the kind's ring length.  Inside the decode program, ``obs:serve/kv_gather`` and
``obs:serve/attend`` (in :mod:`repro.models.attention`) split the paged
read from the attention over it.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.mamba_scan.ops import traced_paths
from repro.models import TransformerLM
from repro.models.attention import paged_kv_len
from repro.obs import MetricsSink, RecompileWatchdog, host_scope
from repro.serve.pool import TRASH_PAGE
from repro.serve.prefill import clear_slot_state, place_paged_prefill
from repro.serve.sampling import sample_tokens
from repro.serve.scheduler import Admission, Request, Scheduler


def narrows_weights(params) -> bool:
    """Whether :func:`serve_weights` may store the dot weights in bfloat16:
    only where that is the arithmetic the program does anyway, with every
    parameter on a TPU (a default-precision float32 dot there is one
    bfloat16 pass whose operands are rounded to nearest even) and the
    default matmul precision in effect."""
    shardings = [getattr(x, "sharding", None)
                 for x in jax.tree.leaves(params)]
    on_tpu = bool(shardings) and all(
        s is not None and all(d.platform == "tpu" for d in s.device_set)
        for s in shardings)
    precision = jax.config.jax_default_matmul_precision
    try:
        default = precision is None or \
            jax.lax.Precision(precision) == jax.lax.Precision.DEFAULT
    except ValueError:                  # an algorithm name, not a precision
        default = False
    return on_tpu and default


def serve_weights(model: TransformerLM, params):
    """The parameter tree with every dot-only weight in bfloat16.

    Attention's ``wq``/``wk``/``wv``/``wo``, the GLU MLPs' ``w_gate``/
    ``w_up``/``w_down`` (shared experts' too) and the head's table become
    ``astype(bfloat16)``; norms, biases, routers, routed experts, recurrent
    blocks and the embedding table the gather reads stay as given.  A tied
    head gets a bfloat16 copy of the embedding table as its own
    ``lm_head``.  Returns a new tree that shares the leaves it keeps with
    ``params``, which is not touched.
    """
    cfg = model.cfg

    def bf16(x):
        return x.astype(jnp.bfloat16)

    def layer(p, blk, ffn):
        p = dict(p)
        if blk in ("attn", "swa"):
            p["mix"] = {k: bf16(v) if k in ("wq", "wk", "wv", "wo") else v
                        for k, v in p["mix"].items()}
        if ffn == "dense":
            p["ffn"] = jax.tree.map(bf16, p["ffn"])
        elif ffn == "moe" and "shared" in p["ffn"]:
            p["ffn"] = dict(p["ffn"],
                            shared=jax.tree.map(bf16, p["ffn"]["shared"]))
        return p

    out = dict(params)
    if cfg.head_layers():
        out["head_layers"] = {
            f"h{i}": layer(params["head_layers"][f"h{i}"], blk, ffn)
            for i, (blk, ffn) in enumerate(cfg.head_layers())}
    out["groups"] = {
        f"l{i}": layer(params["groups"][f"l{i}"], blk, ffn)
        for i, (blk, ffn) in enumerate(cfg.group_pattern())}
    out["lm_head"] = {"table": bf16(model._unembed_table(params))}
    return out


def _nbytes(tree, dtype=None) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if dtype is None or x.dtype == dtype)


def init_carry(model: TransformerLM, max_batch: int, num_pages: dict,
               page_size: int, *, quantized: bool, seed: int):
    """The decode step's device-resident carry, every slot empty."""
    b = max_batch
    return {
        "cache": model.init_paged_cache(b, num_pages, page_size,
                                        quantized=quantized),
        "tok": jnp.zeros((b, 1), jnp.int32),
        "pos": jnp.zeros((b,), jnp.int32),
        "active": jnp.zeros((b,), bool),
        "limit": jnp.zeros((b,), jnp.int32),
        "temp": jnp.zeros((b,), jnp.float32),
        "key": jax.random.PRNGKey(seed),
        "step": jnp.int32(0),
    }


def make_step(model: TransformerLM, *, max_len: int, eos: int):
    """The engine's decode step: ``step(params, carry, tables) -> (carry,
    (2, B) int32 of sampled tokens and the next active mask)``."""

    # the program keeps the name ``step``: its module is ``jit_step``
    def step(params, carry, tables):
        pos, active = carry["pos"], carry["active"]
        with jax.named_scope("obs:serve/sample"):
            sub = jax.random.fold_in(carry["key"], carry["step"])
        with jax.named_scope("obs:serve/decode"):
            logits, cache = model.paged_decode_step(
                params, carry["tok"], pos, carry["cache"], tables,
                max_len=max_len)
        with jax.named_scope("obs:serve/sample"):
            nxt = sample_tokens(logits, sub, carry["temp"])
        with jax.named_scope("obs:serve/carry"):
            done = (nxt == eos) | (pos >= carry["limit"])
            still = active & ~done
            out = jnp.stack([jnp.where(active, nxt, -1),
                             still.astype(jnp.int32)])
            carry = dict(
                carry, cache=cache, active=still,
                tok=jnp.where(active, nxt, carry["tok"][:, 0])[:, None],
                pos=jnp.where(active, pos + 1, pos),
                step=carry["step"] + 1)
        return carry, out

    return step


@dataclasses.dataclass
class Completion:
    """One finished request with its open-loop timing (seconds from run
    start; ``arrival`` is in trace clock units — seconds or steps)."""

    rid: int
    cls: str
    s0: int
    max_new: int
    tokens: np.ndarray
    arrival: float
    t_enqueue: float
    t_admit: float
    t_first: float
    t_done: float
    ttft: float                 # first token latency incl. queueing

    @property
    def n_tokens(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def per_token_s(self) -> float:
        """Mean inter-token latency after the first token."""
        if self.n_tokens <= 1:
            return 0.0
        return (self.t_done - self.t_first) / (self.n_tokens - 1)


class ServeEngine:
    """Fixed-shape continuous-batching engine around one TransformerLM.

    Args:
      max_batch: decode batch slots (the compiled program's batch).
      max_len: logical context bound — every request must satisfy
        ``s0 + max_new - 1 <= max_len`` when the arch has full-attention
        layers (sliding-window/recurrent layers are rings/states and don't
        bound request length).
      page_size: tokens per KV page.
      num_pages: pages per kind {"attn": n, "swa": n}; default sizes each
        pool so ``max_batch`` full-length requests fit (never blocks).
      quantized: int8 KV pool (blockwise scales) instead of f32.
      eos: token id that terminates a slot (-1 = never).
    """

    def __init__(self, model: TransformerLM, params, *, max_batch: int,
                 max_len: int, page_size: int = 8,
                 num_pages: dict | None = None, quantized: bool = False,
                 eos: int = -1, seed: int = 0,
                 sink: MetricsSink | None = None,
                 watchdog: RecompileWatchdog | None = None,
                 log_every: int = 64):
        cfg = model.cfg
        if cfg.frontend != "token":
            raise ValueError(
                f"ServeEngine needs a token frontend (got {cfg.frontend!r}) "
                "— prefix-frontend archs have no prompt-only prefill")
        self.model = model
        self.params = params
        self.weight_bytes = _nbytes(self.params)
        self.narrow_weight_bytes = _nbytes(self.params, jnp.bfloat16)
        self.max_batch = max_batch
        self.max_len = max_len
        self.page_size = page_size
        self.quantized = quantized
        self.eos = eos
        # the engine always has a sink: lifecycle trace records are the
        # canonical latency accounting even for in-memory runs
        self.sink = sink if sink is not None else MetricsSink()
        self.log_every = log_every

        blocks = {blk for blk, _ in cfg.head_layers()} | {
            blk for blk, _ in cfg.group_pattern()}
        # layers whose prefill is a scan over every prompt token
        layers = cfg.head_layers() + cfg.group_pattern() * cfg.n_groups
        self._scanned_layers = sum(blk in ("mamba", "rwkv") for blk, _ in layers)
        self._mamba_layers = sum(blk == "mamba" for blk, _ in layers)
        self.kinds = sorted(blocks & {"attn", "swa"})
        self.ring_len = {k: paged_kv_len(cfg, k, max_len) for k in self.kinds}
        self.n_blocks = {k: -(-t // page_size)
                         for k, t in self.ring_len.items()}
        self._kv_gathered = max_batch * sum(self.ring_len.values())
        if num_pages is None:
            num_pages = {k: 1 + max_batch * nb
                         for k, nb in self.n_blocks.items()}
        self.num_pages = {k: num_pages[k] for k in self.kinds}
        self.sched = Scheduler(max_batch, page_size, self.num_pages,
                               self.ring_len)

        b = max_batch
        # device-resident carry: the step advances it in-jit; the host only
        # writes slot rows at admission
        self._carry = init_carry(model, b, self.num_pages, page_size,
                                 quantized=quantized, seed=seed)
        self._tables = {k: jnp.full((b, nb), TRASH_PAGE, jnp.int32)
                        for k, nb in self.n_blocks.items()}
        self._active_np = np.zeros((b,), bool)

        self._slot_tokens: list[list[int]] = [[] for _ in range(b)]
        self._slot_meta: list[dict | None] = [None] * b
        self._steps = 0
        self._admitted = 0
        self._completed = 0
        # compile/steady split from the host spans: a program's first
        # invocation is charged to the compile bucket, everything after is
        # steady state
        self._decode_compiled = False
        self._decode_compile_s = 0.0
        self._decode_steady_s = 0.0
        self._steady_tokens = 0
        self._steady_steps = 0
        self._prefill_seen: set[int] = set()
        self._prefill_compile_s = 0.0
        self._prefill_steady_s = 0.0
        self._prefill_tokens = 0

        self._step_fn = jax.jit(make_step(model, max_len=max_len, eos=eos),
                                donate_argnums=(1,))

        def clear(params, cache, slot):
            with jax.named_scope("obs:serve/clear"):
                return clear_slot_state(self.model, cache, slot)

        self._clear_fn = jax.jit(clear, donate_argnums=(1,))
        self._admit_fns: dict[int, object] = {}
        # prompt lengths whose prefill program, as traced, runs the kernel
        self._kernel_prefills: set[int] = set()
        self.watchdog = watchdog or RecompileWatchdog(label="serve engine")
        self.watchdog.track("serve_decode_step", self._step_fn, allowed=1)
        self.watchdog.track("serve_clear_slot", self._clear_fn, allowed=1)

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, params):
        """Every tree the engine is given is held as :func:`serve_weights`
        makes it, where :func:`narrows_weights` allows."""
        self._params = (serve_weights(self.model, params)
                        if narrows_weights(params) else params)

    # -- compiled programs ----------------------------------------------------

    def _admit_fn(self, s0: int):
        fn = self._admit_fns.get(s0)
        if fn is not None:
            return fn
        model, max_len = self.model, self.max_len

        def admit(params, prompt, cache, rows, slot):
            with jax.named_scope("obs:serve/prefill"), traced_paths() as paths:
                _, pf = model.prefill(params, {"tokens": prompt})
            if paths - {"ref"}:
                self._kernel_prefills.add(s0)
            with jax.named_scope("obs:serve/place"):
                return place_paged_prefill(model, pf, cache, rows, slot, s0,
                                           max_len)

        fn = jax.jit(admit, donate_argnums=(2,))
        self._admit_fns[s0] = fn
        self.watchdog.track(f"serve_admit_s{s0}", fn, allowed=1)
        return fn

    # -- admission ------------------------------------------------------------

    def _admit(self, adm: Admission, now: float) -> None:
        req, slot = adm.req, adm.slot
        s0 = req.s0
        pages_total = sum(len(p) for p in adm.pages.values())
        with host_scope("obs:serve/admit", rid=req.rid, slot=int(slot),
                        prompt_tokens=s0 - 1, pages=pages_total,
                        scan_tokens=(s0 - 1) * self._scanned_layers) as span:
            with host_scope("obs:serve/slot_write"):
                rows = {}
                for kind in self._tables:
                    row = np.full((self.n_blocks[kind],), TRASH_PAGE,
                                  np.int32)
                    pages = adm.pages[kind]
                    row[:len(pages)] = pages
                    rows[kind] = jnp.asarray(row)
                    self._tables[kind] = self._tables[kind].at[slot].set(
                        rows[kind])
            c = self._carry
            with host_scope("obs:serve/admit_call") as call:
                if s0 == 1:
                    # nothing to prefill, but the slot's recurrent rows
                    # still hold the previous request's state
                    cache = self._clear_fn(self.params, c["cache"],
                                           jnp.int32(slot))
                else:
                    fn = self._admit_fn(s0)
                    prompt = jnp.asarray(req.prompt[None, :s0 - 1])
                    cache = fn(self.params, prompt, c["cache"], rows,
                               jnp.int32(slot))
            dt = call.seconds
            span.attrs["scan_kernel_tokens"] = (
                (s0 - 1) * self._mamba_layers if s0 in self._kernel_prefills else 0)
            if s0 != 1:
                with host_scope("obs:serve/admit_wait") as wait:
                    jax.block_until_ready(jax.tree.leaves(cache)[0])
                dt += wait.seconds
            if s0 in self._prefill_seen or s0 == 1:
                self._prefill_steady_s += dt
                self._prefill_tokens += s0 - 1
            else:
                self._prefill_seen.add(s0)
                self._prefill_compile_s += dt

            # the shared decode step produces the request's FIRST token: its
            # input is the last prompt token at position s0-1, so TTFT is
            # the latency of the slot's first decode step
            with host_scope("obs:serve/slot_write"):
                self._carry = dict(
                    c, cache=cache,
                    tok=c["tok"].at[slot, 0].set(int(req.prompt[s0 - 1])),
                    pos=c["pos"].at[slot].set(s0 - 1),
                    active=c["active"].at[slot].set(True),
                    limit=c["limit"].at[slot].set(s0 + req.max_new - 2),
                    temp=c["temp"].at[slot].set(req.temperature))
            self._active_np[slot] = True
            self._slot_tokens[slot] = []
            meta = dict(req=req, t_admit=now, t_first=None, pages=pages_total)
            self._slot_meta[slot] = meta
            self._admitted += 1
            self._trace("admitted", rid=req.rid, cls=req.cls, slot=slot,
                        pages=pages_total, t_s=now)
            self._trace("prefill", rid=req.rid, slot=slot, tokens=s0 - 1,
                        dur_s=dt, t_s=now + dt)

    # -- the decode step ------------------------------------------------------

    def _decode_once(self, completions: list, t0: float, clock: str,
                     enqueue_t: dict) -> None:
        was_active = np.nonzero(self._active_np)[0]
        # each paged kind's ring holds at most its length of a slot's context
        ctx = [self._slot_meta[s]["req"].s0 + len(self._slot_tokens[s])
               for s in was_active]
        live = sum(min(c, t) for t in self.ring_len.values() for c in ctx)
        with host_scope("obs:serve/step", step=self._steps,
                        active=len(was_active), kv_live_tokens=live,
                        kv_gathered_tokens=self._kv_gathered):
            with host_scope("obs:serve/dispatch") as dispatch:
                self._carry, out = self._step_fn(self.params, self._carry,
                                                 self._tables)
            with host_scope("obs:serve/readback") as readback:
                out = np.asarray(out)               # the per-step host sync
            now = time.monotonic() - t0
            with host_scope("obs:serve/emit"):
                self._emit(out, was_active, now, completions, clock,
                           enqueue_t)
        dt = dispatch.seconds + readback.seconds
        if self._decode_compiled:
            self._decode_steady_s += dt
            self._steady_tokens += len(was_active)
            self._steady_steps += 1
        else:
            self._decode_compiled = True
            self._decode_compile_s += dt
        self._steps += 1
        if self._steps % self.log_every == 0:
            self._log_serve(step_ms=dt * 1e3)

    def _emit(self, out: np.ndarray, was_active, now: float,
              completions: list, clock: str, enqueue_t: dict) -> None:
        """Hand one step's tokens to their slots; finish and release the
        slots whose request ended."""
        toks, still = out[0], out[1].astype(bool)
        for slot in was_active:
            self._slot_tokens[slot].append(int(toks[slot]))
            meta = self._slot_meta[slot]
            if meta["t_first"] is None:
                meta["t_first"] = now
                mreq = meta["req"]
                ref = mreq.arrival if clock == "wall" \
                    else enqueue_t[mreq.rid]
                self._trace("first_token", rid=mreq.rid, cls=mreq.cls,
                            slot=int(slot), t_s=now, ttft_s=now - ref)
            if not still[slot]:
                self._active_np[slot] = False
                self._tables_clear(slot)
                req = self.sched.release(slot)
                t_enq = enqueue_t[req.rid]
                ref = req.arrival if clock == "wall" else t_enq
                comp = Completion(
                    rid=req.rid, cls=req.cls, s0=req.s0, max_new=req.max_new,
                    tokens=np.asarray(self._slot_tokens[slot], np.int32),
                    arrival=req.arrival, t_enqueue=t_enq,
                    t_admit=meta["t_admit"], t_first=meta["t_first"],
                    t_done=now, ttft=meta["t_first"] - ref)
                completions.append(comp)
                self._trace("finished", rid=req.rid, cls=req.cls,
                            slot=int(slot), s0=req.s0, tokens=comp.n_tokens,
                            pages=meta["pages"],
                            queued_s=meta["t_admit"] - t_enq,
                            ttft_s=comp.ttft, per_token_s=comp.per_token_s,
                            t_s=now, dur_s=now - meta["t_admit"])
                self._slot_meta[slot] = None
                self._completed += 1

    def _tables_clear(self, slot: int) -> None:
        # a freed slot must write to the trash page again: its pages are
        # about to be handed to the next admission
        for kind in self._tables:
            self._tables[kind] = self._tables[kind].at[slot].set(TRASH_PAGE)

    # -- driving --------------------------------------------------------------

    def run(self, trace: list[Request], *, clock: str = "wall",
            max_steps: int | None = None) -> dict:
        """Drain one open-loop trace; returns the run report.

        ``clock="wall"``: arrivals are seconds of wall time from run start.
        ``clock="steps"``: arrivals are decode-step indices — deterministic,
        for tests and CI smoke runs.
        """
        if clock not in ("wall", "steps"):
            raise ValueError(f"clock must be 'wall'|'steps', got {clock!r}")
        order = sorted(trace, key=lambda r: (r.arrival, r.rid))
        # the lifecycle records' clock starts with this span
        with host_scope("obs:serve/run", clock=clock, requests=len(trace),
                        weight_bytes=self.weight_bytes,
                        narrow_weight_bytes=self.narrow_weight_bytes):
            return self._run(order, clock, max_steps)

    def _run(self, order: list[Request], clock: str,
             max_steps: int | None) -> dict:
        completions: list[Completion] = []
        enqueue_t: dict[int, float] = {}
        t0 = time.monotonic()
        i = 0
        while True:
            now = (time.monotonic() - t0) if clock == "wall" \
                else float(self._steps)
            while i < len(order) and order[i].arrival <= now:
                self.sched.submit(order[i])
                t_enq = time.monotonic() - t0
                enqueue_t[order[i].rid] = t_enq
                self._trace("queued", rid=order[i].rid, cls=order[i].cls,
                            t_s=t_enq)
                i += 1
            while True:
                adm = self.sched.next_admission()
                if adm is None:
                    break
                self._admit(adm, time.monotonic() - t0)
            if self.sched.active_slots == 0:
                if i == len(order) and not self.sched.waiting:
                    break
                if clock == "wall":
                    time.sleep(min(1e-3, max(0.0, order[i].arrival - now)))
                else:
                    self._steps += 1    # idle step advances virtual time
                continue
            self._decode_once(completions, t0, clock, enqueue_t)
            if max_steps is not None and self._steps >= max_steps:
                break
        self.watchdog.check()
        report = self.report(completions, time.monotonic() - t0)
        self._log_serve(step_ms=None)
        return report

    # -- reporting ------------------------------------------------------------

    def report(self, completions: list[Completion], wall_s: float) -> dict:
        from repro.obs.report import serve_latency_summary

        decode_tok_s = (self._steady_tokens / self._decode_steady_s
                        if self._decode_steady_s > 0 else 0.0)
        prefill_tok_s = (self._prefill_tokens / self._prefill_steady_s
                         if self._prefill_steady_s > 0 else 0.0)
        return {
            "completions": completions,
            "latency": serve_latency_summary(self.sink.records("trace")),
            "steps": self._steps,
            "wall_s": wall_s,
            "admitted": self._admitted,
            "completed": self._completed,
            "weight_bytes": self.weight_bytes,
            "narrow_weight_bytes": self.narrow_weight_bytes,
            "decode": {
                "compile_s": self._decode_compile_s,
                "steady_s": self._decode_steady_s,
                "steady_steps": self._steady_steps,
                "steady_tokens": self._steady_tokens,
                "tok_s": decode_tok_s,
            },
            "prefill": {
                "compile_s": self._prefill_compile_s,
                "steady_s": self._prefill_steady_s,
                "tokens": self._prefill_tokens,
                "tok_s": prefill_tok_s,
            },
            "programs": self.watchdog.snapshot(),
        }

    def _trace(self, event: str, **fields) -> None:
        """One lifecycle trace record; ``step`` is the decode-step index."""
        self.sink.log("trace", self._steps, event=event, **fields)

    def _log_serve(self, step_ms: float | None) -> None:
        decode_tok_s = (self._steady_tokens / self._decode_steady_s
                        if self._decode_steady_s > 0 else 0.0)
        self.sink.log(
            "serve", self._steps,
            active_slots=self.sched.active_slots,
            queued=self.sched.queued,
            kv_occupancy=self.sched.occupancy(),
            kv_pages_used=self.sched.pages_used(),
            kv_pages_total=self.sched.pages_total(),
            admitted=self._admitted,
            completed=self._completed,
            decode_tok_s=decode_tok_s,
            step_ms=step_ms,
        )
