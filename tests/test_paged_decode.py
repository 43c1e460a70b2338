"""Block-table-native paged decode: bit-exact parity with the dense-gather
path, (kind x backend x format x layout) registry lookups, page-granular
traffic, and the steady-state loop's freedom from gather/scatter."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import CELL, cell_state_config

from repro import ops as OPS
from repro.configs import get_smoke_config
from repro.core import formats as F
from repro.core.state_update import StateQuantConfig
from repro.models import model as M
from repro.serving.api import ServeConfig
from repro.serving.engine import PagedServingEngine, Request
from repro.serving.memory import PAGE_TOKENS, PagedStatePool, pages_for


def _build(arch, fmt, backend, rounding):
    if arch.endswith(CELL):
        cfg = cell_state_config(arch[:-len(CELL)], backend)
    else:
        cfg = get_smoke_config(arch).with_(
            state_quant=StateQuantConfig(fmt=fmt, rounding=rounding,
                                         backend=backend))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _prefill_pool(params, cfg, prompt_len, n_pages=8, n_slabs=5):
    pool = PagedStatePool(cfg, n_pages=n_pages, n_slabs=n_slabs)
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    pr = jnp.asarray(prompt)[None]
    logits, row = jax.jit(lambda p, b: M.prefill(p, cfg, b))(
        params, {"tokens": pr, "targets": pr})
    assert pool.register(1, pages_for(prompt_len))
    pool.insert_prefill(1, row)
    return pool, int(jnp.argmax(logits[0]))


def _decode_steps(pool, params, tok, length, n_steps):
    """Greedy decode steps over a two-row batch (row 1 idle), growing the
    block table over page boundaries like the engine's headroom check."""
    outs = []
    L = np.array([length, 0], np.int32)
    t = tok
    for step in range(n_steps):
        while L[0] // PAGE_TOKENS + 1 > len(pool.page_table[1]):
            assert pool.grow(1, 1)
        lg = pool.decode(params, [1, None], np.array([t, 0], np.int32),
                         L, seed=step + 1)
        outs.append(np.asarray(lg))
        t = int(jnp.argmax(lg[0]))
        L[0] += 1
    return outs


# two archs (one attention, one SSM) x both backends x lengths straddling
# a page boundary: 127 (tail slot of page 1), 128 (page-exact), 129 (page 2);
# plus the novel pallas kernel branches -- MLA's latent-only cache (dummy V
# refs) and zamba2's shared-attention group re-binding -- on the boundary pair
PARITY_MATRIX = [
    (arch, fmt, backend, L)
    for arch in ("llama3.2-1b", "mamba2-2.7b")
    for fmt, backend in (("mx8", "pallas"), ("mx8", "jnp"),
                         ("fp32", "jnp"))
    for L in (127, 128, 129)
] + [
    (arch, "mx8", "pallas", L)
    for arch in ("deepseek-v2-236b", "zamba2-2.7b")
    for L in (127, 129)
] + [
    # the chat cells' state heads (80 of 64; dk 128 and 64), updated in
    # place in the pool's stored layout
    ("mamba2-2.7b" + CELL, "mx8", "pallas", 129),
    ("mamba2-2.7b" + CELL, "mx8", "jnp", 129),
    ("zamba2-2.7b" + CELL, "mx8", "pallas", 127),
]


@pytest.mark.parametrize(
    "arch,fmt,backend,length", PARITY_MATRIX,
    ids=[f"{a}-{f}-{b}-L{L}" for a, f, b, L in PARITY_MATRIX])
def test_paged_decode_bit_identical_to_dense_gather(arch, fmt, backend,
                                                    length):
    """Steady-state paged decode must produce bit-identical logits to the
    dense-gather reference path, across the page boundary."""
    rounding = "stochastic" if fmt == "mx8" else "nearest"
    params, cfg = _build(arch, fmt, backend, rounding)
    pool, tok = _prefill_pool(params, cfg, length)
    snapshot = [np.asarray(x) for x in pool.pools]
    pages0 = list(pool.page_table[1])

    pool.decode_mode = "gather"
    ref = _decode_steps(pool, params, tok, length, n_steps=2)

    pool.pools = [jnp.asarray(x) for x in snapshot]
    grown = [p for p in pool.page_table[1] if p not in pages0]
    if grown:
        pool.placement.free(grown)
    pool.page_table[1] = list(pages0)
    pool.decode_mode = "paged"
    got = _decode_steps(pool, params, tok, length, n_steps=2)

    for step, (a, b) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(
            a, b, err_msg=f"{arch}/{fmt}/{backend}/L={length} step {step}")


# state heads (H, dv, dk) of the kernel-level in-place check: the two chat
# cells', the smoke models', and a retnet-like width of two row tiles
IN_PLACE_HEADS = [(80, 64, 128), (80, 64, 64), (8, 16, 16), (2, 64, 32),
                  (2, 512, 256)]


@pytest.mark.parametrize("heads,dv,dk", IN_PLACE_HEADS,
                         ids=[f"H{h}-dv{v}-dk{k}" for h, v, k in IN_PLACE_HEADS])
def test_paged_state_update_in_place_matches_gathered_rows(heads, dv, dk):
    """The in-place kernel over a slab pool equals the dense kernel on the
    gathered rows, bit for bit: live slabs in no sorted order, pad rows all
    on the scratch slab 0, one layer of two.  Slabs and layers no row owns
    are left as they were; the stochastic-rounding counter is the batch
    row's."""
    from repro.kernels import mx_state_update as SU
    from repro.ops import interpret_pallas
    n_slabs, layer = 6, 1
    slabs = jnp.asarray([4, 0, 2, 0, 5], jnp.int32)
    live = np.asarray(slabs) != 0
    B = slabs.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(dk), 6)
    logical = F.mx8_quantize(jax.random.normal(
        ks[0], (n_slabs, 2, heads, dv, dk)))
    pool = F.QuantizedTensor("mx8", logical.shape, {
        f: SU.store(f, a) for f, a in logical.payload.items()})
    d = jax.nn.sigmoid(jax.random.normal(ks[1], (B, heads, 1)))
    k = jax.random.normal(ks[2], (B, heads, dk))
    v = jax.random.normal(ks[3], (B, heads, dv))
    q = jax.random.normal(ks[4], (B, heads, dk))
    seed = jnp.int32(17)

    new_pool, y = SU.mx_paged_state_update(
        pool, slabs, layer, d, k, v, q, seed, interpret=interpret_pallas())
    rows = F.QuantizedTensor("mx8", (B, heads, dv, dk), {
        f: a[slabs, layer] for f, a in logical.payload.items()})
    ref_rows, ref_y = SU.mx_state_update(rows, d, k, v, q, seed,
                                         interpret=interpret_pallas())
    jnp_rows, _ = OPS.state_update_step(
        rows, d, k, v, q, StateQuantConfig(fmt="mx8", rounding="stochastic",
                                           backend="jnp"), seed=seed)
    for f, a in new_pool.payload.items():
        got = SU.unstore(f, a, heads, dv, dk)
        want = logical.payload[f].at[slabs[live], layer].set(
            ref_rows.payload[f][live])
        untouched = np.ones(n_slabs, bool)
        untouched[np.asarray(slabs)] = False
        np.testing.assert_array_equal(got[untouched], want[untouched])
        np.testing.assert_array_equal(got[:, 0], want[:, 0])
        np.testing.assert_array_equal(got[slabs[live], layer],
                                      ref_rows.payload[f][live], err_msg=f)
        np.testing.assert_array_equal(ref_rows.payload[f],
                                      jnp_rows.payload[f], err_msg=f)
    np.testing.assert_array_equal(y[live], ref_y[live])


def test_state_update_ops_fall_back_where_no_block_is_lane_aligned():
    """A head whose stored rows have no 128-lane tile that fits the
    kernel's budget (mlstm's 1040 rows of 1024) runs the jnp reference:
    the pallas ops give its bits, and the paged op reports it gathers."""
    from repro.kernels import mx_state_update as SU
    H, dv, dk, B = 1, 1040, 1024, 2
    assert SU.plan_blocks(H, dv, dk) is None
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    logical = F.mx8_quantize(jax.random.normal(ks[0], (3, 1, H, dv, dk)))
    slabs = jnp.asarray([2, 1], jnp.int32)
    rows = F.QuantizedTensor("mx8", (B, H, dv, dk), {
        f: a[slabs, 0] for f, a in logical.payload.items()})
    inputs = {"d": jax.nn.sigmoid(jax.random.normal(ks[1], (B, H, 1))),
              "k": jax.random.normal(ks[2], (B, H, dk)),
              "v": jax.random.normal(ks[3], (B, H, dv)),
              "q": jax.random.normal(ks[4], (B, H, dk)), "seed": 9}
    quant = lambda b: StateQuantConfig(fmt="mx8", rounding="stochastic",
                                       backend=b)
    want, y_want = OPS.state_update_step(rows, **inputs, cfg=quant("jnp"))
    got, y_got = OPS.state_update_step(rows, **inputs, cfg=quant("pallas"))
    pool = OPS.PagedState(F.QuantizedTensor("mx8", logical.shape, {
        f: SU.store(f, a) for f, a in logical.payload.items()}),
        slabs, jnp.int32(0))
    paged, y_paged = OPS.state_update_step(pool, **inputs,
                                           cfg=quant("pallas"))
    for f in want.payload:
        np.testing.assert_array_equal(got.payload[f], want.payload[f])
        np.testing.assert_array_equal(
            SU.unstore(f, paged.pool.payload[f][slabs, 0], H, dv, dk),
            want.payload[f])
    np.testing.assert_array_equal(y_got, y_want)
    np.testing.assert_array_equal(y_paged, y_want)
    assert not OPS.get_op("state_update", "pallas", "mx8",
                          "paged").in_place(H, dv, dk)


@pytest.mark.parametrize("backend,want", [("pallas", (1, 0)),
                                          ("jnp", (0, 1))])
def test_state_slab_gauges_count_in_place_updates(backend, want):
    """The pool reports whether the decode step updates its recurrent-state
    leaves in place (pallas) or gathers them (the jnp reference)."""
    from repro.obs import Observability
    cfg = get_smoke_config("mamba2-2.7b").with_(
        state_quant=StateQuantConfig(fmt="mx8", rounding="stochastic",
                                     backend=backend))
    pool = PagedStatePool(cfg, n_pages=3, n_slabs=3)
    obs = Observability()
    pool.attach_obs(obs)
    assert tuple(obs.metrics.value(f"state_slab_leaves_{how}")
                 for how in ("in_place", "gathered")) == want


# ---------------------------------------------------------------------------
# registry: the layout axis
# ---------------------------------------------------------------------------

def test_registry_lookup_errors_list_quadruples():
    """(kind x backend x format x layout) lookup failures name the
    registered quadruples, layout included."""
    with pytest.raises(KeyError) as ei:
        OPS.get_op("attn_decode", "pallas", "fp32", "paged")
    msg = str(ei.value)
    assert "layout 'paged'" in msg
    assert "attn_decode[pallas:mx8:paged]" in msg
    assert "attn_decode[jnp:fp32:dense]" in msg

    with pytest.raises(ValueError, match="layout 'paged'"):
        OPS.resolve_backend("attn_decode", "fp32", "pallas",
                            layout="paged", strict=True)
    # negotiation is per-layout: fp32 paged falls back to the jnp paged op
    assert OPS.resolve_backend("attn_decode", "fp32", "pallas",
                               layout="paged") == "jnp"
    with pytest.raises(ValueError, match="unknown op layout"):
        class Bad(OPS.SpuOp):
            kind = "attn_decode"
            backend = "jnp"
            formats = ("fp32",)
            layout = "ragged"
        OPS.register(Bad)


def test_paged_plans_carry_layout():
    cfg = get_smoke_config("llama3.2-1b")
    dense = OPS.decode_op_plans(cfg, 2, 200)
    paged = OPS.decode_op_plans(cfg, 2, 200, layout="paged")
    assert {e.plan.layout for e in dense} == {"dense"}
    assert {e.plan.layout for e in paged} == {"paged"}


def test_paged_attention_traffic_is_page_granular():
    """A 129-token context streams two whole pages under the paged ops;
    the append writes one row regardless of context length."""
    quant = OPS.StateQuantConfig(fmt="mx8", rounding="nearest", backend="jnp")
    dims = dict(B=2, T=129, KVH=2, dk=64, dv=64, n=1, H=4)
    paged = OPS.traffic(OPS.plan_attn_decode_dims(
        "attn_decode", dims, quant, layout="paged"))
    dense = OPS.traffic(OPS.plan_attn_decode_dims("attn_decode", dims, quant))
    bits = OPS.fmt_bits("mx8")
    row_vals = 2 * (64 + 64)
    assert paged.state_read == pytest.approx(2 * 2 * PAGE_TOKENS * row_vals
                                             * bits / 8.0)
    assert dense.state_read == pytest.approx(2 * 129 * row_vals * bits / 8.0)
    ap = OPS.traffic(OPS.plan("kv_append", dims, quant, "jnp",
                              layout="paged"))
    ad = OPS.traffic(OPS.plan("kv_append", dims, quant, "jnp"))
    assert ap.state_write == pytest.approx(ad.state_write)  # one row each
    dims_big = dict(dims, T=4 * PAGE_TOKENS)
    ap_big = OPS.traffic(OPS.plan("kv_append", dims_big, quant, "jnp",
                                  layout="paged"))
    assert ap_big.state_write == pytest.approx(ap.state_write)


# ---------------------------------------------------------------------------
# engine-level: donation, retraces, residual gather accounting
# ---------------------------------------------------------------------------

def test_paged_engine_decode_no_retrace():
    """The paged engine's retrace pin: a constant-shape workload
    (equal-length prompts in one page bucket, the full decode batch, same
    shape as benchmarks/paged_smoke.py) must be served by exactly one
    compiled decode executable.  The recompile
    watcher must agree with the jit cache, and tag only warmup compiles."""
    cfg = get_smoke_config("llama3.2-1b").with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=4, n_pages=9, n_slabs=9, prefill_chunk=128))
    rng = np.random.default_rng(0)
    for i in range(4):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab_size, 12
                                               ).astype(np.int32),
                           max_new_tokens=4))
    done = eng.run()
    assert len(done) == 4 and all(len(r.output) == 4 for r in done)
    assert eng.pool._decode.n_compiles == 1, "paged decode retraced"
    assert eng.obs.recompiles.counts().get("pool.decode", 0) == 1
    assert eng.obs.recompiles.n_recompiles == 0, \
        [e.changed for e in eng.obs.recompiles.events if not e.is_warmup]
    # the step series separates the one compile step from steady state
    stats = eng.stats()
    assert stats["compile_steps"] >= 1.0
    assert stats["recompiles"] == float(len(eng.obs.recompiles.events))
    assert sum(eng.step_compiled) == int(stats["compile_steps"])


def test_paged_engine_gather_bytes_only_at_the_edges():
    """Steady-state decode moves zero gather/scatter bytes: the ledger grows
    only at prefill insertion (and spill/resume), never per decode step."""
    cfg = get_smoke_config("llama3.2-1b").with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=2, n_pages=7, n_slabs=5))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 17)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    eng._admit()
    after_prefill = eng.pool.gather_bytes
    expected = sum(eng.pool.request_nbytes(pages_for(len(p)))
                   for p in prompts)
    assert after_prefill == pytest.approx(expected)
    done = eng.run()
    assert len(done) == 2 and eng.preemptions == 0
    assert eng.pool.gather_bytes == pytest.approx(after_prefill), \
        "decode steps moved gather/scatter bytes"
    stats = eng.stats()
    assert stats["gather_bytes"] == pytest.approx(after_prefill)
    assert any(k.startswith("op_traffic_bytes/") for k in stats)


def test_paged_engine_spill_resume_accounts_gather_bytes(tmp_path):
    """Preemption still rides gather/scatter -- and is accounted as such."""
    cfg = get_smoke_config("llama3.2-1b").with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=2, n_pages=4, n_slabs=5, prefill_chunk=128))
    rng = np.random.default_rng(3)
    for i in range(2):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab_size, 120
                                               ).astype(np.int32),
                           max_new_tokens=12))
    done = eng.run()
    assert len(done) == 2 and eng.preemptions >= 1
    # every preemption costs one spill + one resume on top of the prefills
    min_expected = (2 + 2 * eng.preemptions) * eng.pool.request_nbytes(1)
    assert eng.pool.gather_bytes >= min_expected
