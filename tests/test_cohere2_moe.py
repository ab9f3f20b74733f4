"""Cohere2-MoE on the CPU at test widths: the paged kernels' first key and
window against ``paged_attention_ref`` (interpret mode), the interleaved rotary
and the LayerNorm, the program's prefill through BOTH kinds of page against the
plain reference's full forward (logits), the reference against
``transformers``' ``cohere2`` module with the same weights, the eight shares
against the uncut layer, the seeded weights, and every control.

Tolerances: the reference is float32 at ``Precision.HIGHEST``; the program
multiplies in bfloat16 on a float32 residual stream, which at these widths
leaves the logits 0.2-0.6% apart (measured here; the limit is 2%, three times
the largest).  A control has to read at least twice that limit."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import reference_cohere2_moe as ref
from benchmarks.correctness import rel_rms
from githubrepostorag_tpu.models import cohere2_moe as program
from githubrepostorag_tpu.models.cohere2_moe import Cohere2MoeConfig, init_params
from githubrepostorag_tpu.ops.paged_attention import paged_attention_ref

LIMIT = 0.02


def model_of(cfg: Cohere2MoeConfig) -> dict:
    """The reference's view of a program configuration: the source's keys."""
    return {"hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
            ref.KV_HEADS: cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "intermediate_size": cfg.intermediate_size, "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok, "experts_held": cfg.experts_held,
            "num_shared_experts": cfg.num_shared_experts, "num_hidden_layers": cfg.num_layers,
            "layer_switch": cfg.layer_switch, "sliding_window": cfg.sliding_window,
            "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
            "layer_norm_eps": cfg.layer_norm_eps, "logit_scale": cfg.logit_scale,
            "norm_topk_prob": cfg.norm_topk_prob}


# ------------------------------------------------------------------ kernels --

def _pools(rng, n_kv, pages, ps, hd, layers=None):
    shape = (n_kv, pages, ps, hd) if layers is None else (layers, n_kv, pages, ps, hd)
    return (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16),
            jnp.asarray(rng.standard_normal(shape), jnp.bfloat16))


@pytest.mark.parametrize("narrow", [False, True], ids=["f32-products", "bf16-products"])
@pytest.mark.parametrize("races", [False, True], ids=["interpret", "tpu-interpreter"])
def test_the_burst_kernel_walks_from_a_rows_first_key(races, narrow):
    """Starts inside a page, at a page boundary, at 0, and past ``pool_len`` (a
    dead row), over tables indexed by absolute page whose entries before the
    start name a page of garbage: against the oracle given the same window."""
    from jax.experimental.pallas import tpu as pltpu

    from githubrepostorag_tpu.ops.pallas_paged import paged_attention_decode_staged

    rng = np.random.default_rng(0)
    b, n_kv, group, hd, ps, pages, steps = 8, 2, 4, 128, 16, 64, 4
    kp, vp = _pools(rng, n_kv, pages, ps, hd, layers=2)
    kp = kp.at[:, :, 0].set(1e4)  # page 0 is what released entries name: never read
    table = np.zeros((b, 12), np.int32)
    lens = np.array([100, 96, 40, 7, 0, 150, 64, 33], np.int32)
    starts = np.array([37, 48, 0, 0, 0, 149, 64, 90], np.int32)  # row 6, 7: nothing in the pool
    for r in range(b):
        first, held = starts[r] // ps, -(-lens[r] // ps)
        table[r, first:held] = 1 + rng.permutation(pages - 1)[:max(0, held - first)]
    q = jnp.asarray(rng.standard_normal((b, 1, n_kv * group, hd)), jnp.bfloat16)
    sk = jnp.asarray(rng.standard_normal((b, n_kv, steps, hd)), jnp.bfloat16)
    sv = jnp.asarray(rng.standard_normal((b, n_kv, steps, hd)), jnp.bfloat16)
    interpret = pltpu.InterpretParams(detect_races=True, dma_execution_mode="on_wait") \
        if races else True
    layer, staged = jnp.int32(1), 2
    got = paged_attention_decode_staged(
        q, kp, vp, jnp.asarray(table), jnp.asarray(lens), sk, sv, jnp.asarray([staged]),
        layer, interpret=interpret, pool_starts=jnp.asarray(starts), bf16_products=narrow)
    # the oracle: the pool's keys in [start, len) and the staged tail, dense
    from githubrepostorag_tpu.ops.attention import dense_attention
    from githubrepostorag_tpu.ops.paged_attention import gather_kv

    pk, pv = gather_kv(kp[1], vp[1], jnp.asarray(table))
    at = np.arange(pk.shape[1])[None, :]
    valid = np.concatenate([(at < lens[:, None]) & (at >= starts[:, None]),
                            np.broadcast_to(np.arange(steps) < staged, (b, steps))], axis=1)
    want = dense_attention(q, jnp.concatenate([pk, sk.swapaxes(1, 2)], axis=1),
                           jnp.concatenate([pv, sv.swapaxes(1, 2)], axis=1), causal=False,
                           kv_valid=jnp.asarray(valid))
    # bfloat16 products round the softmax weights to 8 bits: 0.4% of a value a key
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=4e-2 if narrow else 2e-2, rtol=2e-2)
    # and with no start at all the kernel is the one it was
    plain = paged_attention_decode_staged(
        q, kp, vp, jnp.asarray(np.where(table == 0, 1, table)), jnp.asarray(lens), sk, sv,
        jnp.asarray([staged]), layer, interpret=True)
    assert np.isfinite(np.asarray(plain, np.float32)).all()


@pytest.mark.parametrize("narrow", [False, True], ids=["f32-products", "bf16-products"])
@pytest.mark.parametrize("window", [24, 32, 33, 200])
def test_the_wave_kernel_masks_and_skips_what_lies_behind_the_window(window, narrow):
    """A chunk of queries over a cached prefix: keys at or before ``p - window``
    are masked a query, pages wholly behind the lowest query's window are not
    read (their table entries name a page of garbage).  Windows that end inside
    a page, at a page boundary, one past it, and longer than the context."""
    from githubrepostorag_tpu.ops.fused_decode import fused_window_attention

    rng = np.random.default_rng(1)
    b, n_kv, group, hd, ps, pages, s = 3, 2, 2, 128, 16, 48, 8
    kp, vp = _pools(rng, n_kv, pages, ps, hd)
    kp = kp.at[:, 0].set(1e4)
    cached = np.array([70, 5, 0], np.int32)
    new = np.array([8, 8, 3], np.int32)
    table = np.zeros((b, 8), np.int32)
    for r in range(b):
        first = max(0, cached[r] - window + 1) // ps
        held = -(-(cached[r] + new[r]) // ps)
        table[r, first:held] = 1 + rng.permutation(pages - 1)[:held - first]
    q = jnp.asarray(rng.standard_normal((b, s, n_kv * group, hd)), jnp.bfloat16)
    args = (q, kp, vp, jnp.asarray(table), jnp.asarray(cached), jnp.asarray(new))
    got = fused_window_attention(*args, interpret=True, sliding=window, bf16_products=narrow)
    want = paged_attention_ref(*args, sliding=window)
    live = np.arange(s)[None, :] < new[:, None]
    np.testing.assert_allclose(np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
                               atol=4e-2 if narrow else 2e-2, rtol=2e-2)
    if window < 70:  # the window matters: the same call without it reads the garbage page
        assert not np.allclose(np.asarray(paged_attention_ref(*args), np.float32)[0],
                               np.asarray(want, np.float32)[0], atol=1e-1)


def test_interleaved_rotary_and_the_layer_norm_are_the_published_modules():
    torch = pytest.importorskip("torch")
    mod = pytest.importorskip("transformers.models.cohere2.modeling_cohere2")
    from githubrepostorag_tpu.ops.norms import layer_norm
    from githubrepostorag_tpu.ops.rope import rope_cos_sin_interleaved, rope_rotate_interleaved

    rng = np.random.default_rng(2)
    x = rng.standard_normal((2, 5, 3, 16)).astype(np.float32)
    pos = np.array([[0, 1, 2, 3, 4], [30000, 30001, 30002, 30003, 30004]], np.int32)
    cos, sin = rope_cos_sin_interleaved(jnp.asarray(pos), 16, 50000.0)
    got = rope_rotate_interleaved(jnp.asarray(x), cos[:, :, None], sin[:, :, None])
    tq = torch.tensor(x).permute(0, 2, 1, 3)  # [B, heads, S, hd]
    want, _ = mod.apply_rotary_pos_emb(tq, tq, torch.tensor(np.asarray(cos)),
                                       torch.tensor(np.asarray(sin)))
    np.testing.assert_allclose(np.asarray(got), want.permute(0, 2, 1, 3).numpy(), atol=1e-5)
    # the reference's own form (pairs by reshape) agrees with the program's (rolls)
    mine = ref.rope_interleaved(jnp.asarray(x[1]), jnp.asarray(pos[1]), 50000.0)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(got[1]), atol=1e-5)

    norm = mod.Cohere2LayerNorm(hidden_size=16, eps=1e-5)
    w = rng.standard_normal(16).astype(np.float32)
    norm.weight.data = torch.tensor(w)
    h = rng.standard_normal((4, 16)).astype(np.float32) * 3 + 1
    np.testing.assert_allclose(np.asarray(layer_norm(jnp.asarray(h), jnp.asarray(w), 1e-5)),
                               norm(torch.tensor(h)).detach().numpy(), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.layer_norm(jnp.asarray(h), 1e-5, jnp.asarray(w))),
                               norm(torch.tensor(h)).detach().numpy(), atol=1e-5)


# ------------------------------------------------- the program vs the reference --

CFG = Cohere2MoeConfig.tiny(experts_held=(0, 8))


def _prefill(cfg, params, seqs, chunk=32, ps=16, sliding_pages=64):
    """Next-token logits of the program's prefill, chunk by chunk through both
    pools (absolute tables, nothing released)."""
    from githubrepostorag_tpu.serving.kv_cache import make_page_pools

    rb, per = len(seqs), -(-max(map(len, seqs)) // ps)
    g = make_page_pools(cfg, rb * per, ps)
    s = make_page_pools(cfg, sliding_pages, ps, layers=cfg.sliding_layers)
    pools = [g.k, g.v, s.k, s.v]
    bt = np.arange(rb * per, dtype=np.int32).reshape(rb, per)
    out = np.zeros((rb, cfg.vocab_size), np.float32)
    for start in range(0, max(map(len, seqs)), chunk):
        ids = np.zeros((rb, chunk), np.int32)
        slots = np.full((rb, chunk), -1, np.int32)
        cached, lens = np.zeros((rb,), np.int32), np.zeros((rb,), np.int32)
        for i, seq in enumerate(seqs):
            valid = max(0, min(len(seq) - start, chunk))
            ids[i, :valid] = seq[start:start + valid]
            pos = start + np.arange(valid)
            slots[i, :valid] = bt[i, pos // ps] * ps + pos % ps
            cached[i], lens[i] = (start, valid) if valid else (0, 0)
        pos2 = np.broadcast_to(start + np.arange(chunk, dtype=np.int32), (rb, chunk))
        logits, pools[0], pools[1], _, pools[2], pools[3] = program.forward_paged(
            params, cfg, jnp.asarray(ids), jnp.asarray(pos2), pools[0], pools[1],
            jnp.asarray(slots), jnp.asarray(bt), jnp.asarray(cached), jnp.asarray(lens),
            logits_at=jnp.asarray(np.maximum(lens - 1, 0)), sliding_k=pools[2], sliding_v=pools[3],
            sliding_slots=jnp.asarray(slots), sliding_tables=jnp.asarray(bt))
        for i, seq in enumerate(seqs):
            if start < len(seq) <= start + chunk:
                out[i] = np.asarray(logits[i, 0], np.float32)
    return out


@pytest.fixture(scope="module")
def sequences():
    rng = np.random.default_rng(3)
    return [rng.integers(2, CFG.vocab_size, n).tolist() for n in (150, 70)]  # both past the window


class Peaked:
    """The seeded weights with the query and key projections times 8: at test
    widths a draw of std 0.02 leaves every score near zero and attention near
    uniform, where neither a window nor a rotation moves the result much; at
    the published widths scores have std ~1.6 as drawn."""

    def __init__(self, control=None, cfg=None, seed=11):
        self.w = ref.Weights(model_of(cfg or CFG), seed, control)

    def mat(self, name, *index, **kw):
        return self.w.mat(name, *index, **kw) * (8.0 if name in ("wq", "wk") else 1.0)

    def embed(self, ids):
        return self.w.embed(ids)


@pytest.fixture(scope="module")
def reference_logits(sequences):
    model = model_of(CFG)
    return {c: np.stack([r[0] for r in ref.logits_at(
        model, 11, sequences, [[len(s) - 1] for s in sequences], control=c, q_block=32,
        weights=Peaked(c))]) for c in (None, "fp8", *ref.KNOCK_OUTS)}


def test_the_seeded_weights_are_the_programs_leaf_for_leaf():
    params = init_params(CFG, seed=11)
    w, lay = ref.Weights(model_of(CFG), 11), params["layers"]
    d, h, nkv, hd = CFG.hidden_size, CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
    assert [n for n, _, _ in ref.leaf_order(model_of(CFG))] == [
        p[-1] for p, _, _ in program.leaf_order(CFG)]
    np.testing.assert_array_equal(np.asarray(w.embed(np.arange(7))),
                                  np.asarray(params["embed"][:7], np.float32))
    for li in (0, CFG.num_layers - 1):
        wqkv = np.asarray(lay["wqkv"][li], np.float32)
        np.testing.assert_array_equal(np.asarray(w.mat("wq", li)), wqkv[:, :h * hd])
        np.testing.assert_array_equal(np.asarray(w.mat("wv", li)), wqkv[:, (h + nkv) * hd:])
        np.testing.assert_array_equal(np.asarray(w.mat("router", li)),
                                      np.asarray(lay["router"][li], np.float32))
        np.testing.assert_array_equal(np.asarray(w.mat("e_wd", li, 3, rows=(4, 9))),
                                      np.asarray(lay["e_wd"][li, 3, 4:13], np.float32))
        np.testing.assert_array_equal(np.asarray(w.mat("s_wgu", li, cols=(5, 40))),
                                      np.asarray(lay["s_wgu"][li, :, 5:45], np.float32))
    assert float(np.abs(np.asarray(lay["router"], np.float32)).max()) > 0.05  # the gain of 2


def test_prefill_through_both_pools_gives_the_references_logits(sequences, reference_logits):
    params = init_params(CFG, seed=11)
    h, nkv, hd = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
    peak = jnp.concatenate([jnp.full(((h + nkv) * hd,), 8.0), jnp.ones((nkv * hd,))])
    params["layers"]["wqkv"] = (params["layers"]["wqkv"] * peak.astype(jnp.bfloat16))  # ``Peaked``
    got = _prefill(CFG, params, sequences)
    err = rel_rms(got, reference_logits[None])
    print("prefill_logits_rel_rms", err)
    assert err < LIMIT


@pytest.mark.parametrize("control", ["fp8", *ref.KNOCK_OUTS])
def test_each_control_reads_not_correct(control, reference_logits):
    """The reference at the precision below, and with one piece of the
    mathematics knocked out, against the plain one: each is far past the limit
    the program passes (and ``no_window`` differs at all only because the
    sequences are longer than the window)."""
    err = rel_rms(reference_logits[control], reference_logits[None])
    print(control, err)
    assert err > 2 * LIMIT


def test_the_eight_shares_and_the_shared_experts_once_are_the_uncut_layer():
    """The guide's share test: the routed parts of all shares of the experts
    (here 4 shares of 4, and 2 of 8), with what every chip computes alike (the
    shared experts' mean) counted once, add up to the uncut layer; and the
    program's expert layer, told it holds a share, computes that share's part."""
    cfg = Cohere2MoeConfig.tiny(num_layers=4)
    whole = model_of(cfg)
    w = ref.Weights(whole, 5)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((24, cfg.hidden_size)), jnp.float32)
    uncut = ref.routed_part(w, whole, 2, x) + ref.shared_part(w, whole, 2, x)

    class Share:
        """Experts [lo, hi) of the uncut draw, as a share's weights."""

        def __init__(self, lo):
            self.lo = lo

        def mat(self, name, *index, **kw):
            if name.startswith("e_"):
                index = (index[0], index[1] + self.lo)
            return w.mat(name, *index, **kw)

    for n in (4, 8):
        parts = sum(ref.routed_part(Share(lo), {**whole, "experts_held": (lo, lo + n)}, 2, x)
                    for lo in range(0, cfg.num_experts, n))
        np.testing.assert_allclose(np.asarray(parts + ref.shared_part(w, whole, 2, x)),
                                   np.asarray(uncut), atol=1e-5)
    # the program's layer on a share: the same part, shared experts included once
    held = (4, 8)
    share_cfg = Cohere2MoeConfig.tiny(num_layers=4, experts_held=held)
    params = init_params(Cohere2MoeConfig.tiny(num_layers=4), seed=5)  # the uncut draw
    lay = {k: v for k, v in params["layers"].items()}
    experts = {k: lay[k][:, held[0]:held[1]] for k in ("e_wgu", "e_wd")}
    p = jax.tree.map(lambda a: a[2], {k: v for k, v in lay.items() if k not in experts})
    xb = x.astype(jnp.bfloat16)[None]
    got, stats = program._moe_ffn(share_cfg, p, experts, 2, xb, jnp.ones((1, 24), bool))
    want = ref.routed_part(Share(held[0]), {**whole, "experts_held": held}, 2, xb[0].astype(
        jnp.float32)) + ref.shared_part(w, whole, 2, xb[0].astype(jnp.float32))
    assert rel_rms(np.asarray(got[0]), np.asarray(want)) < LIMIT
    assert int(stats[1]) > 0


def test_the_reference_is_transformers_cohere2_with_the_experts_standing_down():
    """The published dense sibling (``transformers.models.cohere2``) with the
    same weights: one shared expert of the dense width and no routed expert is
    its MLP.  Held here: the norm, the interleaved rotary and where it applies
    (sliding layers only), both masks, the parallel residual, the tied head and
    ``logit_scale``."""
    torch = pytest.importorskip("torch")
    from transformers import Cohere2Config, Cohere2ForCausalLM

    d, h, nkv, hd, ff, L, v, window = 32, 4, 2, 8, 48, 4, 96, 6
    hf_cfg = Cohere2Config(
        vocab_size=v, hidden_size=d, intermediate_size=ff, num_hidden_layers=L,
        num_attention_heads=h, num_key_value_heads=nkv, head_dim=hd, layer_norm_eps=1e-5,
        rope_theta=50000.0, sliding_window=window, sliding_window_pattern=4, logit_scale=0.5,
        tie_word_embeddings=True, attention_bias=False, max_position_embeddings=128,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        attn_implementation="eager", pad_token_id=0)
    torch.manual_seed(0)
    hf = Cohere2ForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for p_ in hf.parameters():
            p_.copy_(torch.randn_like(p_) * (0.3 if p_.ndim > 1 else 1.0))
    sd = {k: t.detach().numpy().astype(np.float32) for k, t in hf.state_dict().items()}
    layer = lambda i, name: sd[f"model.layers.{i}.{name}.weight"]  # noqa: E731 - [out, in]

    class Published:
        """The module's weights under the reference's names ([in, out])."""

        def mat(self, name, li, *rest, rows=None, cols=None):
            full = {"wq": lambda: layer(li, "self_attn.q_proj").T,
                    "wk": lambda: layer(li, "self_attn.k_proj").T,
                    "wv": lambda: layer(li, "self_attn.v_proj").T,
                    "wo": lambda: layer(li, "self_attn.o_proj").T,
                    "s_wgu": lambda: np.concatenate([layer(li, "mlp.gate_proj").T,
                                                     layer(li, "mlp.up_proj").T], axis=1),
                    "s_wd": lambda: layer(li, "mlp.down_proj").T}[name]()
            r0, nr = rows or (0, full.shape[0])
            c0, nc = cols or (0, full.shape[1])
            return jnp.asarray(full[r0:r0 + nr, c0:c0 + nc])

        def embed(self, ids):
            return jnp.asarray(sd["model.embed_tokens.weight"][np.asarray(ids)])

    model = {"hidden_size": d, "num_attention_heads": h, ref.KV_HEADS: nkv, "head_dim": hd,
             "intermediate_size": ff, "num_experts": 0, "num_experts_per_tok": 0,
             "experts_held": (0, 0), "num_shared_experts": 1, "num_hidden_layers": L,
             "layer_switch": 4, "sliding_window": window, "vocab_size": v, "rope_theta": 50000.0,
             "layer_norm_eps": 1e-5, "logit_scale": 0.5}
    ids = np.random.default_rng(6).integers(1, v, 24)
    norms = jnp.asarray(np.stack([layer(i, "input_layernorm") for i in range(L)]))
    hid = ref.forward(model, Published(), ids, q_block=8, norms=norms)[:len(ids)]
    mine = np.asarray(ref.layer_norm(hid, 1e-5, jnp.asarray(sd["model.norm.weight"]))
                      @ sd["model.embed_tokens.weight"].T) * 0.5
    with torch.no_grad():
        theirs = hf(torch.tensor(ids[None])).logits[0].numpy()
    np.testing.assert_allclose(mine, theirs, atol=2e-3, rtol=2e-3)
    # the knock-outs move this model too: the window and the rotary are live in it
    for control in ("no_window", "rope_everywhere"):
        other = ref.forward(model, Published(), ids, control=control, q_block=8, norms=norms)
        assert float(jnp.abs(other[:len(ids)] - hid).max()) > 1e-2, control
