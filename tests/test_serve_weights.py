"""The serving engine's own weights, and the dots that read them.

* :func:`repro.serve.engine.serve_weights` stores every dot-only weight in
  bfloat16 (a tied head gets a copy of its own) and leaves everything else,
  and the caller's tree, as they are.
* :func:`repro.serve.engine.narrows_weights` allows that only on a TPU at the
  default matmul precision: on the CPU the engine keeps the caller's float32
  tree and counts no narrow bytes.
* :func:`repro.models.layers.weight_einsum` leaves every float32 program as
  it was, reads a bfloat16 weight as one bfloat16 product accumulated in
  float32, and paged decode stays bit-equal to contiguous decode on the
  narrow weights.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_arch
from repro.models import TransformerLM
from repro.models import attention, layers, transformer
from repro.serve import Request, ServeEngine
from repro.serve import engine as engine_mod
from repro.serve.engine import narrows_weights, serve_weights
from test_serve import _paged_setup

BF16, F32 = jnp.bfloat16, jnp.float32
ATTN = {"wq", "wk", "wv", "wo"}
MLP = {"w_gate", "w_up", "w_down"}


def _model(arch):
    model = TransformerLM(get_arch(arch, smoke=True))
    return model, model.init(jax.random.PRNGKey(0))


def _flat(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def _is_dot_weight(path: str) -> bool:
    """Attention and GLU weights; routed experts keep float32."""
    key = path.split("'")[-2]                  # "[...]['mix']['wq']"
    if "['experts']" in path:
        return False
    return (key in ATTN and "['mix']" in path) or (
        key in MLP and "['ffn']" in path)


# -- the weight preparation ----------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen2_0_5b", "h2o_danube_1_8b",
                                  "deepseek_moe_16b", "jamba_1_5_large_398b"])
def test_serve_weights_narrows_only_dot_weights(arch):
    model, params = _model(arch)
    before = {k: np.asarray(v) for k, v in _flat(params).items()}
    out = serve_weights(model, params)

    got = _flat(out)
    head = "['lm_head']['table']"
    assert set(got) == set(before) | {head}
    narrowed = set()
    for path, old in before.items():
        new = got[path]
        if _is_dot_weight(path) or path == head:
            assert new.dtype == BF16, path
            np.testing.assert_array_equal(
                np.asarray(new), np.asarray(jnp.asarray(old).astype(BF16)))
            narrowed.add(path)
        else:       # norms, biases, routers, experts, recurrent blocks
            assert new is _flat(params)[path], path
    assert any("['wq']" in p for p in narrowed)
    assert any("['w_down']" in p for p in narrowed)
    if model.cfg.moe is not None and model.cfg.moe.num_shared:
        assert any("['shared']" in p for p in narrowed)
    # the head reads its own bfloat16 table; the gather keeps float32
    emb = "['embedding']['table']"
    assert got[emb].dtype == F32
    source = emb if model.cfg.tie_embeddings else head
    np.testing.assert_array_equal(
        np.asarray(got[head]),
        np.asarray(jnp.asarray(before[source]).astype(BF16)))
    assert model._unembed_table(out) is out["lm_head"]["table"]
    # the caller's tree is untouched
    if model.cfg.tie_embeddings:
        assert "lm_head" not in params
    for path, v in _flat(params).items():
        assert v.dtype == before[path].dtype
        np.testing.assert_array_equal(np.asarray(v), before[path])


# -- when the engine narrows ---------------------------------------------------

def _requests(vocab):
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, vocab, (s0,)).astype(np.int32),
                    max_new=n, arrival=float(a))
            for i, (s0, n, a) in enumerate([(6, 5, 0), (10, 4, 0), (6, 3, 2)])]


def _run(engine, vocab):
    obs.clear_spans()
    report = engine.run(_requests(vocab), clock="steps")
    run = [s for s in obs.spans() if s.name == "obs:serve/run"][-1]
    return report, run


@pytest.mark.parametrize("precision", [None, "highest"],
                         ids=["default", "highest"])
def test_engine_keeps_float32_off_the_tpu(precision):
    model, params = _model("qwen2_0_5b")
    with jax.default_matmul_precision(precision):
        assert not narrows_weights(params)
        engine = ServeEngine(model, params, max_batch=2, max_len=24,
                             page_size=4)
    assert engine.params is params
    report, run = _run(engine, model.cfg.vocab)
    total = 4 * model.num_params()
    for got in (report, run.attrs):
        assert got["narrow_weight_bytes"] == 0
        assert got["weight_bytes"] == total


def test_narrow_engine_counts_its_bytes_and_serves_one_program(monkeypatch):
    """The narrow path forced on the CPU: the counter is the bytes of the
    bfloat16 leaves, and the engine's tokens are those of batch-1 greedy
    decoding over the same narrow tree, from one decode program."""
    from repro.launch.serve import greedy_generate

    model, params = _model("qwen2_0_5b")
    cfg = model.cfg
    monkeypatch.setattr(engine_mod, "narrows_weights", lambda p: True)
    engine = ServeEngine(model, params, max_batch=2, max_len=24, page_size=4)
    attn = cfg.d_model * (cfg.n_heads + 2 * cfg.n_kv_heads) \
        * cfg.resolved_head_dim + cfg.n_heads * cfg.resolved_head_dim \
        * cfg.d_model
    dots = cfg.n_layers * (attn + 3 * cfg.d_model * cfg.d_ff)
    head = cfg.vocab * cfg.d_model                   # the tied head's copy
    narrow = 2 * (dots + head)
    assert engine.narrow_weight_bytes == narrow
    assert engine.weight_bytes == 4 * (model.num_params() - dots) + narrow

    report, run = _run(engine, cfg.vocab)
    for got in (report, run.attrs):
        assert got["narrow_weight_bytes"] == narrow
        assert got["weight_bytes"] == engine.weight_bytes
    assert report["programs"]["serve_decode_step"] == 1
    tokens = {c.rid: c.tokens for c in report["completions"]}
    for r in _requests(cfg.vocab):
        ref = greedy_generate(model, engine.params,
                              jnp.asarray(r.prompt[None]), r.max_new,
                              use_prefill=True)
        np.testing.assert_array_equal(tokens[r.rid], np.asarray(ref[0]))


# -- the dots ------------------------------------------------------------------

def _today(spec, x, w, dt):
    """Every weight dot's expression before bfloat16 weights existed."""
    return jnp.einsum(spec, x.astype(dt), w.astype(dt))


def _glu_jaxprs(model, params):
    p = jax.tree.map(lambda a: a[0], params["groups"]["l0"]["ffn"])
    x = jnp.ones((2, 3, model.cfg.d_model), F32)
    return lambda: jax.make_jaxpr(
        lambda p, x: layers.glu_mlp(p, x, F32))(p, x)


def _qkv_jaxprs(model, params):
    p = jax.tree.map(lambda a: a[0], params["groups"]["l0"]["mix"])
    x = jnp.ones((2, 3, model.cfg.d_model), F32)
    pos = jnp.arange(3, dtype=jnp.int32)
    return lambda: jax.make_jaxpr(
        lambda p, x: attention._project_qkv(p, x, model.cfg, pos))(p, x)


def _decode_jaxprs(model, params):
    from repro.serve.engine import init_carry, make_step

    carry = init_carry(model, 2, {"attn": 13}, 4, quantized=False, seed=0)
    tables = {"attn": jnp.zeros((2, 6), jnp.int32)}
    step = make_step(model, max_len=24, eos=-1)
    return lambda: jax.make_jaxpr(step)(params, carry, tables)


def _loss_jaxprs(model, params):
    batch = {"tokens": jnp.zeros((2, 9), jnp.int32)}
    return lambda: jax.make_jaxpr(model.loss)(params, batch)


@pytest.mark.parametrize("program", [_glu_jaxprs, _qkv_jaxprs, _decode_jaxprs,
                                     _loss_jaxprs],
                         ids=["glu_mlp", "project_qkv", "decode", "loss"])
def test_float32_weights_keep_todays_jaxpr(program, monkeypatch):
    model, params = _model("qwen2_0_5b")
    make = program(model, params)
    now = str(make())
    for mod in (layers, attention, transformer):
        monkeypatch.setattr(mod, "weight_einsum", _today)
    assert now == str(make())
    assert "bf16" not in now


SPECS = [("bsd,dhk->bshk", (2, 3, 64), (64, 4, 16)),     # wq / wk / wv
         ("bshk,hkd->bsd", (2, 3, 4, 16), (4, 16, 64)),  # wo
         ("...d,df->...f", (2, 3, 64), (64, 96)),        # w_gate / w_up
         ("bd,vd->bv", (2, 64), (512, 64))]              # the head


@pytest.mark.parametrize("spec,xs,ws", SPECS, ids=[s[0] for s in SPECS])
def test_bfloat16_weight_is_one_bfloat16_product(spec, xs, ws):
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, xs, F32)
    w = jax.random.normal(kw, ws, F32).astype(BF16)
    got = layers.weight_einsum(spec, x, w, F32)
    assert got.dtype == F32
    ref = jnp.einsum(spec, x.astype(BF16).astype(F32), w.astype(F32),
                     precision=jax.lax.Precision.HIGHEST)
    k = int(np.prod(ws)) // int(np.prod(got.shape[-1:]))
    # products of bfloat16 values are exact in float32; only the order of
    # the float32 sum may differ
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=0,
                               atol=4 * k * np.finfo(np.float32).eps
                               * float(jnp.max(jnp.abs(ref))))


@pytest.mark.parametrize("arch", ["qwen2_0_5b", "gemma2_27b", "rwkv6_7b",
                                  "jamba_1_5_large_398b"])
def test_paged_decode_bit_equals_contiguous_on_narrow_weights(arch):
    model, params = _model(arch)
    params = serve_weights(model, params)
    b, max_len, page_size, steps = 2, 24, 4, 20
    contiguous = model.init_cache(b, max_len)
    paged, tables = _paged_setup(model, b, max_len, page_size)
    dense = jax.jit(model.decode_step)
    sparse = jax.jit(model.paged_decode_step, static_argnames=("max_len",))
    rng = np.random.default_rng(0)
    pos_v = jnp.zeros((b,), jnp.int32)
    for t in range(steps):
        tok = jnp.asarray(rng.integers(0, model.cfg.vocab, (b, 1)), jnp.int32)
        ref, contiguous = dense(params, tok, jnp.int32(t), contiguous)
        got, paged = sparse(params, tok, pos_v, paged, tables,
                            max_len=max_len)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        pos_v = pos_v + 1
