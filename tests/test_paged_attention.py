"""paged_attention kernel-op tests: reference-vs-pallas parity (GQA,
ragged page tails), the chunked-prefill supertile kernel (s > 1, int8
fused dequant), dispatch resolution, dequant-on-gather, and nn-level
equivalence with the dense ring-buffer decode path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.configs.base import AttnConfig
from repro.kernels.paged_attention import (
    gather_pages,
    paged_attention_decode,
    paged_attention_prefill,
    paged_attention_ref,
)
from repro.nn import attention as attn
from repro.nn import kvquant
from repro.nn.spec import init_params

KEY = jax.random.PRNGKey(11)


def _setup(b=3, h=4, kvh=2, d=16, ps=8, num_pages=16, width=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (1, kvh, num_pages, ps, d), jnp.float32)
    vp = jax.random.normal(ks[2], (1, kvh, num_pages, ps, d), jnp.float32)
    # distinct pages per sequence, null-page padding in the tail
    table = jnp.array(
        [[1, 2, 3, 4], [5, 6, 7, 0], [8, 9, 0, 0]][:b], jnp.int32
    )[:, :width]
    lengths = jnp.array([29, 23, 9][:b], jnp.int32)  # ragged tails
    return q, kp, vp, table, lengths


@pytest.mark.parametrize("kvh", [1, 2, 4])  # MQA / GQA / MHA
def test_kernel_matches_reference_gqa(kvh):
    q, kp, vp, table, lengths = _setup(kvh=4)
    kp, vp = kp[:, :kvh], vp[:, :kvh]
    ref = paged_attention_ref(q, kp, vp, table, lengths - 1, lengths, 0)
    got = paged_attention_decode(
        q[:, 0], kp, vp, table, lengths - 1, lengths, 0, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref[:, 0]), rtol=1e-5, atol=1e-5
    )


def test_kernel_ragged_tail_and_softcap():
    q, kp, vp, table, lengths = _setup()
    lengths = jnp.array([25, 17, 1], jnp.int32)  # incl. a 1-token sequence
    ref = paged_attention_ref(q, kp, vp, table, lengths - 1, lengths, 0,
                              softcap=8.0)
    got = paged_attention_decode(
        q[:, 0], kp, vp, table, lengths - 1, lengths, 0, softcap=8.0,
        interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref[:, 0]), rtol=1e-5, atol=1e-5
    )


def test_gather_pages_layout():
    kp = jnp.arange(2 * 4 * 3 * 2, dtype=jnp.float32).reshape(2, 4, 3, 2)
    table = jnp.array([[2, 0], [1, 3]], jnp.int32)
    g = gather_pages(kp, table)
    assert g.shape == (2, 6, 2, 2)  # (b, n*ps, kvh, d)
    np.testing.assert_array_equal(np.asarray(g[0, 0, 0]), np.asarray(kp[0, 2, 0]))
    np.testing.assert_array_equal(np.asarray(g[1, 4, 1], ), np.asarray(kp[1, 3, 1]))


def test_multi_token_reference_matches_contiguous_attention():
    """A bucket-padded suffix 'prefill' through the paged reference must
    equal ordinary causal attention over the contiguous sequence."""
    b, h, kvh, d, ps = 1, 4, 2, 16, 8
    total, start_pos, s_pad = 21, 16, 8  # 5 true suffix tokens, padded to 8
    ks = jax.random.split(KEY, 3)
    k_all = jax.random.normal(ks[0], (b, total, kvh, d), jnp.float32)
    v_all = jax.random.normal(ks[1], (b, total, kvh, d), jnp.float32)
    q_suf = jax.random.normal(ks[2], (b, s_pad, h, d), jnp.float32)

    # pages 1..3 hold the contiguous sequence (ragged tail in page 3)
    kp = jnp.zeros((1, kvh, 8, ps, d), jnp.float32)
    vp = jnp.zeros((1, kvh, 8, ps, d), jnp.float32)
    pad = jnp.pad(k_all, ((0, 0), (0, 24 - total), (0, 0), (0, 0)))
    kp = kp.at[0, :, 1:4].set(pad[0].transpose(1, 0, 2).reshape(kvh, 3, ps, d))
    pad_v = jnp.pad(v_all, ((0, 0), (0, 24 - total), (0, 0), (0, 0)))
    vp = vp.at[0, :, 1:4].set(pad_v[0].transpose(1, 0, 2).reshape(kvh, 3, ps, d))

    table = jnp.array([[1, 2, 3]], jnp.int32)
    start = jnp.array([start_pos], jnp.int32)
    lengths = jnp.array([total], jnp.int32)
    got = paged_attention_ref(q_suf, kp, vp, table, start, lengths, 0)

    # oracle: dense masked attention over the contiguous k/v
    g = h // kvh
    q5 = q_suf.reshape(b, s_pad, kvh, g, d)
    logits = jnp.einsum("bskgh,btkh->bkgst", q5, k_all).astype(jnp.float32)
    logits = logits / np.sqrt(d)
    qp = start_pos + jnp.arange(s_pad)
    mask = jnp.arange(total)[None, :] <= qp[:, None]
    logits = jnp.where(mask[None, None, None], logits, -2.0**30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_all.dtype)
    want = jnp.einsum("bkgst,btkh->bskgh", probs, v_all).reshape(b, s_pad, h, d)

    # only the 5 true suffix rows are meaningful (padded rows discarded)
    np.testing.assert_allclose(
        np.asarray(got[:, :5]), np.asarray(want[:, :5]), rtol=1e-5, atol=1e-5
    )


def test_dispatch_resolution():
    shape1 = (3, 1, 4, 2, 4, 8, 16, 0)
    r = kernels.resolve("paged_attention", shape1, jnp.float32)
    assert r.backend == "reference"  # off-TPU default
    r = kernels.resolve("paged_attention", shape1, jnp.float32, policy="pallas")
    assert r.schedule == "pallas" and not r.vjp
    # multi-token (suffix prefill) and int8-scale problems resolve to
    # the chunked-prefill supertile schedule under forced pallas; the
    # decode kernel's availability keeps it to s==1 bf16/fp32
    for shape in [(3, 8, 4, 2, 4, 8, 16, 0), (3, 1, 4, 2, 4, 8, 16, 2)]:
        r = kernels.resolve(
            "paged_attention", shape, jnp.float32, policy="pallas"
        )
        assert r.schedule == "pallas_prefill" and not r.vjp
        decode = kernels.op("paged_attention").schedule("pallas")
        assert not decode.available(kernels.Problem(shape, "float32"))
    # the supertile schedule autotunes its q-chunk from the problem
    r = kernels.resolve(
        "paged_attention", (3, 64, 4, 2, 4, 8, 16, 0), jnp.float32,
        policy="pallas",
    )
    assert r.schedule == "pallas_prefill" and r.cfg.get("qc", 0) >= 1


def test_forced_pallas_runs_prefill_and_int8_calls():
    """The PR-4-era availability guards are gone: forced backend=pallas
    multi-token and int8 calls run the supertile kernel and track the
    reference gather."""
    q, kp, vp, table, lengths = _setup()
    q8 = jnp.broadcast_to(q, (q.shape[0], 8, *q.shape[2:]))
    want = paged_attention_ref(q8, kp, vp, table, lengths - 8, lengths, 0)
    got = kernels.op("paged_attention")(
        q8, kp, vp, table, lengths - 8, lengths, 0, policy="pallas"
    )
    valid = np.asarray(lengths) - np.asarray(lengths - 8)
    for bi, n in enumerate(valid):
        np.testing.assert_allclose(
            np.asarray(got[bi, :n]), np.asarray(want[bi, :n]),
            rtol=1e-5, atol=1e-5,
        )
    kq, ks = kvquant.quantize_kv(kp)
    vq, vs = kvquant.quantize_kv(vp)
    want8 = paged_attention_ref(
        q, kq, vq, table, lengths - 1, lengths, 0, k_scale=ks, v_scale=vs
    )
    got8 = kernels.op("paged_attention")(
        q, kq, vq, table, lengths - 1, lengths, 0, ks, vs, policy="pallas"
    )
    np.testing.assert_allclose(
        np.asarray(got8, np.float32), np.asarray(want8, np.float32),
        rtol=1e-2, atol=1e-2,  # the reference rounds its output to bf16
    )
    # forcing the decode schedule BY NAME on a multi-token problem is
    # still a clear error (it would silently drop tokens otherwise)
    with pytest.raises(ValueError, match="pallas_prefill"):
        kernels.op("paged_attention")(
            q8, kp, vp, table, lengths - 8, lengths, 0,
            policy="schedule=pallas"
        )


def test_registry_call_matches_direct_reference():
    q, kp, vp, table, lengths = _setup()
    want = paged_attention_ref(q, kp, vp, table, lengths - 1, lengths, 0)
    got = kernels.op("paged_attention")(q, kp, vp, table, lengths - 1,
                                        lengths, 0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    forced = kernels.op("paged_attention")(
        q, kp, vp, table, lengths - 1, lengths, 0, policy="pallas"
    )
    np.testing.assert_allclose(
        np.asarray(forced), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_dequant_on_gather_matches_dequantized_pages():
    q, kp, vp, table, lengths = _setup()
    kq, ks = kvquant.quantize_kv(kp)
    vq, vs = kvquant.quantize_kv(vp)
    got = paged_attention_ref(
        q, kq, vq, table, lengths - 1, lengths, 0, k_scale=ks, v_scale=vs
    )
    want = paged_attention_ref(
        q, kvquant.dequantize_kv(kq, ks), kvquant.dequantize_kv(vq, vs),
        table, lengths - 1, lengths, 0,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=1e-2, atol=1e-2,
    )


# ---------------------------------------------------------------------------
# chunked-prefill supertile kernel (s > 1, int8 fused dequant)
# ---------------------------------------------------------------------------


def _prefill_setup(b=3, h=4, kvh=2, d=16, ps=8, num_pages=16, s=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (1, kvh, num_pages, ps, d), jnp.float32)
    vp = jax.random.normal(ks[2], (1, kvh, num_pages, ps, d), jnp.float32)
    table = jnp.array([[1, 2, 3, 4], [5, 6, 7, 0], [8, 9, 0, 0]][:b], jnp.int32)
    lengths = jnp.array([29, 23, 9][:b], jnp.int32)
    start = lengths - jnp.array([5, 8, 3][:b], jnp.int32)  # ragged suffixes
    return q, kp, vp, table, start, lengths


@pytest.mark.parametrize("kvh", [1, 2, 4])  # MQA / GQA / MHA
def test_prefill_kernel_matches_reference_gqa(kvh):
    q, kp, vp, table, start, lengths = _prefill_setup(kvh=4)
    kp, vp = kp[:, :kvh], vp[:, :kvh]
    ref = paged_attention_ref(q, kp, vp, table, start, lengths, 0)
    got = paged_attention_prefill(
        q, kp, vp, table, start, lengths, 0, interpret=True
    )
    for bi in range(q.shape[0]):
        n = int(lengths[bi] - start[bi])  # rows past the true suffix are
        got_b, ref_b = got[bi, :n], ref[bi, :n]  # discarded upstream
        np.testing.assert_allclose(
            np.asarray(got_b), np.asarray(ref_b), rtol=1e-5, atol=1e-5
        )


@pytest.mark.parametrize("qc", [1, 2, 3, 8])  # incl. non-dividing chunks
def test_prefill_kernel_chunk_sizes_and_softcap(qc):
    q, kp, vp, table, start, lengths = _prefill_setup()
    ref = paged_attention_ref(q, kp, vp, table, start, lengths, 0,
                              softcap=8.0)
    got = paged_attention_prefill(
        q, kp, vp, table, start, lengths, 0, softcap=8.0, qc=qc,
        interpret=True
    )
    for bi in range(q.shape[0]):
        n = int(lengths[bi] - start[bi])
        np.testing.assert_allclose(
            np.asarray(got[bi, :n]), np.asarray(ref[bi, :n]),
            rtol=1e-5, atol=1e-5,
        )


def test_prefill_kernel_int8_fused_dequant():
    """int8 pages + per-slot scales dequantise in-kernel on the gather,
    tracking the reference backend's dequant-on-gather (which rounds its
    output through bf16 — hence the bf16-level tolerance)."""
    q, kp, vp, table, start, lengths = _prefill_setup()
    kq, ks = kvquant.quantize_kv(kp)
    vq, vs = kvquant.quantize_kv(vp)
    ref = paged_attention_ref(
        q, kq, vq, table, start, lengths, 0, k_scale=ks, v_scale=vs
    )
    got = paged_attention_prefill(
        q, kq, vq, table, start, lengths, 0, k_scale=ks, v_scale=vs, qc=4,
        interpret=True,
    )
    for bi in range(q.shape[0]):
        n = int(lengths[bi] - start[bi])
        np.testing.assert_allclose(
            np.asarray(got[bi, :n], np.float32),
            np.asarray(ref[bi, :n], np.float32),
            rtol=1e-2, atol=1e-2,
        )


def test_prefill_kernel_s1_matches_decode_kernel():
    """On the decode problem (s == 1) the supertile kernel degenerates to
    the decode kernel's math exactly."""
    q, kp, vp, table, lengths = _setup()
    dec = paged_attention_decode(
        q[:, 0], kp, vp, table, lengths - 1, lengths, 0, interpret=True
    )
    pre = paged_attention_prefill(
        q, kp, vp, table, lengths - 1, lengths, 0, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(pre[:, 0]), np.asarray(dec), rtol=1e-6, atol=1e-6
    )


def test_prefill_kernel_chunked_calls_match_one_shot():
    """Chunked-vs-contiguous oracle at the kernel level: running the
    suffix as separate per-chunk kernel calls (each at its true start
    position) equals the one-shot call with the same q-chunk — chunk
    boundaries are invisible to the supertile grid."""
    q, kp, vp, table, start, lengths = _prefill_setup(b=1, s=8)
    one = paged_attention_prefill(
        q, kp, vp, table, start, lengths, 0, qc=4, interpret=True
    )
    parts = [
        paged_attention_prefill(
            q[:, c0 : c0 + 4], kp, vp, table, start + c0, lengths, 0,
            qc=4, interpret=True,
        )
        for c0 in (0, 4)
    ]
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate(parts, axis=1)), np.asarray(one)
    )


# ---------------------------------------------------------------------------
# nn-level: paged vs. dense ring-buffer decode
# ---------------------------------------------------------------------------


def _attn_setup(ps=8, width=4, seed=2):
    cfg = AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16)
    params = init_params(attn.attn_spec(32, cfg), jax.random.PRNGKey(seed))
    return cfg, params


def test_paged_decode_matches_dense_decode():
    """Same context, same new token: the paged path and the dense ring
    path produce identical outputs (fp32 math over bf16 cache bytes)."""
    cfg, params = _attn_setup()
    b, ps, width, slots = 2, 8, 4, 32
    ctx_lens = np.array([13, 21])
    dense = attn.init_cache(b, slots, cfg)
    paged = attn.init_paged_cache(1 + b * width, ps, cfg)
    table = np.zeros((b, width), np.int32)
    table[0, :width] = np.arange(1, 1 + width)
    table[1, :width] = np.arange(1 + width, 1 + 2 * width)

    # build identical contexts token by token through both paths
    x_ctx = jax.random.normal(KEY, (b, int(ctx_lens.max()), 32), jnp.float32)
    for t in range(int(ctx_lens.max())):
        active = ctx_lens > t
        idx = jnp.full((b,), t, jnp.int32)
        _, dense = attn.decode_attention(
            params, x_ctx[:, t : t + 1], dense, cfg, index=idx
        )
        _, paged = attn.paged_decode_attention(
            params, x_ctx[:, t : t + 1], paged, cfg, index=idx,
            block_table=jnp.asarray(table),
            lengths=jnp.asarray(np.where(active, t + 1, ctx_lens), jnp.int32),
        )
    # dense wrote every slot to max ctx len; rewind pos for the short
    # sequence so both caches describe the same ragged contexts
    pos_fix = jnp.where(
        jnp.arange(slots)[None, :] < jnp.asarray(ctx_lens)[:, None],
        dense.pos, -1,
    )
    dense = dense._replace(pos=pos_fix)

    x_new = jax.random.normal(jax.random.PRNGKey(5), (b, 1, 32), jnp.float32)
    out_d, _ = attn.decode_attention(
        params, x_new, dense, cfg, index=jnp.asarray(ctx_lens, jnp.int32)
    )
    out_p, _ = attn.paged_decode_attention(
        params, x_new, paged, cfg, index=jnp.asarray(ctx_lens, jnp.int32),
        block_table=jnp.asarray(table),
        lengths=jnp.asarray(ctx_lens + 1, jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(out_d, np.float32), np.asarray(out_p, np.float32),
        rtol=1e-5, atol=1e-5,
    )


def test_paged_decode_rejects_windows():
    cfg, params = _attn_setup()
    paged = attn.init_paged_cache(8, 8, cfg)
    x = jnp.zeros((1, 1, 32), jnp.float32)
    with pytest.raises(NotImplementedError):
        attn.paged_decode_attention(
            params, x, paged, cfg, index=jnp.int32(0),
            block_table=jnp.zeros((1, 2), jnp.int32),
            lengths=jnp.ones((1,), jnp.int32), window=16,
        )


@pytest.mark.parametrize("chunk", [1, 3, 4])
def test_nn_chunked_suffix_prefill_matches_one_shot(chunk):
    """Chunked-vs-contiguous oracle at the attention level: feeding a
    suffix through ``paged_decode_attention`` in chunks leaves the page
    pool bitwise-identical to the one-shot call, and each token's output
    matches (the engine's chunked-prefill correctness argument)."""
    cfg, params = _attn_setup()
    b, ps, width, start, total = 1, 8, 4, 10, 21  # 11-token ragged suffix
    table = jnp.array([[1, 2, 3]], jnp.int32)
    x = jax.random.normal(KEY, (b, total - start, 32), jnp.float32)

    one = attn.init_paged_cache(8, ps, cfg)
    out_one, one = attn.paged_decode_attention(
        params, x, one, cfg, index=jnp.int32(start),
        block_table=table, lengths=jnp.asarray([total], jnp.int32),
    )
    chunked = attn.init_paged_cache(8, ps, cfg)
    outs = []
    for c0 in range(0, total - start, chunk):
        xc = x[:, c0 : c0 + chunk]
        o, chunked = attn.paged_decode_attention(
            params, xc, chunked, cfg, index=jnp.int32(start + c0),
            block_table=table,
            lengths=jnp.asarray([start + c0 + xc.shape[1]], jnp.int32),
        )
        outs.append(o)
    for a, c in zip(jax.tree.leaves(one), jax.tree.leaves(chunked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(outs, 1), np.float32),
        np.asarray(out_one, np.float32), rtol=1e-5, atol=1e-5,
    )


def test_quant_paged_tracks_bf16_paged():
    cfg, params = _attn_setup()
    b, ps, width = 1, 8, 3
    paged16 = attn.init_paged_cache(8, ps, cfg)
    paged8 = kvquant.init_quant_paged_cache(8, ps, cfg)
    table = jnp.array([[1, 2, 3]], jnp.int32)
    outs16, outs8 = [], []
    x = jax.random.normal(KEY, (b, 12, 32), jnp.float32)
    for t in range(12):
        idx = jnp.full((b,), t, jnp.int32)
        ln = jnp.full((b,), t + 1, jnp.int32)
        o16, paged16 = attn.paged_decode_attention(
            params, x[:, t : t + 1], paged16, cfg, index=idx,
            block_table=table, lengths=ln,
        )
        o8, paged8 = kvquant.quant_paged_decode_attention(
            params, x[:, t : t + 1], paged8, cfg, index=idx,
            block_table=table, lengths=ln,
        )
        outs16.append(o16)
        outs8.append(o8)
    a = np.asarray(jnp.concatenate(outs16, 1), np.float32)
    c = np.asarray(jnp.concatenate(outs8, 1), np.float32)
    np.testing.assert_allclose(a, c, rtol=0.25, atol=0.25)  # int8 noise bound


# ---------------------------------------------------------------------------
# the stacked pool: in-place page write, packed heads, the model's steps
# ---------------------------------------------------------------------------


def _write_case(dtype, start, s, length, b=3, L=3, G=2, P=14, ps=4, W=8,
                width=5):
    """Random stacked pools and the write coordinates of ``s`` new rows
    per batch row from position ``start`` (row b's ``length`` valid
    tokens; the last row is idle), as the decode step computes them."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    pools = [jax.random.normal(k, (L, G, P, ps, W), jnp.float32).astype(dtype)
             for k in ks[:2]]
    new = [jax.random.normal(k, (b, s, G, W), jnp.float32).astype(dtype)
           for k in ks[2:]]
    table = jnp.arange(1, 1 + b * width, dtype=jnp.int32).reshape(b, width)
    lengths = jnp.array([length, min(length, start + 1), 0][:b], jnp.int32)
    table = table.at[b - 1].set(0)
    index = jnp.full((b,), start, jnp.int32)
    _, slot, rows, valid = attn.paged_positions(new[0], index, lengths, ps,
                                                width)
    ids = jnp.where(valid, jnp.take_along_axis(table, slot, axis=1), 0)
    return pools, new, ids, rows


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("start,s,length", [
    (6, 1, 7),  # a decode row
    (2, 11, 13),  # a suffix chunk over four pages, fully valid
    (3, 11, 9),  # the chunk's tail past the length: bucket padding
    (0, 16, 13),  # a cold prompt: whole pages but the last
], ids=["decode", "chunk", "chunk_padded", "cold"])
def test_page_write_kernel_matches_reference(dtype, start, s, length):
    """The aliased Pallas page write (interpret mode) stores every valid
    row where the reference scatter does, bit for bit, in the given
    layer only; padded and idle rows reach no page but the null page."""
    pools, new, ids, rows = _write_case(dtype, start, s, length)
    layer = jnp.int32(1)
    got = kernels.op("page_write")(*pools, *new, ids, rows, layer,
                                   policy="pallas")
    want = kernels.op("page_write")(*pools, *new, ids, rows, layer,
                                    policy="reference")
    for g, w, before in zip(got, want, pools):
        np.testing.assert_array_equal(np.asarray(g[:, :, 1:]),
                                      np.asarray(w[:, :, 1:]))
        # only the pages of valid rows changed, and only in that layer
        live = np.unique(np.asarray(ids)[np.asarray(ids) > 0])
        changed = np.flatnonzero(np.any(
            np.asarray(g != before), axis=(0, 1, 3, 4)))
        assert set(changed) - {0} <= set(live)
        np.testing.assert_array_equal(np.asarray(g[0]), np.asarray(before[0]))
        np.testing.assert_array_equal(np.asarray(g[2]), np.asarray(before[2]))


def test_touched_pages_bound_covers_every_run():
    """The grid's page count is a bound: a valid run starting anywhere
    in a page, then padding, never needs more slots than it gives."""
    from repro.kernels.paged_attention import touched_pages
    ps = 4
    for s in (1, 2, 5, 11, 16):
        for start in range(ps):
            for n_valid in range(s + 1):
                pos = start + np.arange(s)
                ids = np.where(np.arange(s) < n_valid, 1 + pos // ps, 0)
                slot, pages = touched_pages(jnp.asarray(ids[None]), ps)
                assert int(slot.max()) < pages.shape[1]
                np.testing.assert_array_equal(
                    np.asarray(pages)[0, np.asarray(slot)[0]], ids)


@pytest.mark.parametrize("kvh,hd", [(2, 64), (8, 32), (4, 64)])
def test_packed_pool_reads_per_head(kvh, hd):
    """Lane-packed pages round-trip: rows written packed read back per
    head as the unpacked pool holds them, and both paged kernels and the
    reference give the same attention over either layout."""
    pack = attn.packed_heads(kvh, hd)
    assert pack == 128 // hd
    b, h, ps, P, s = 2, 2 * kvh, 8, 9, 3
    groups = kvh // pack
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    unpacked = [jax.random.normal(k, (1, kvh, P, ps, hd), jnp.float32)
                for k in ks[:2]]
    new = jax.random.normal(ks[2], (b, s, kvh, hd), jnp.float32)
    ids = jnp.array([[3, 3, 4], [6, 6, 0]], jnp.int32)
    rows = jnp.array([[6, 7, 0], [1, 2, 0]], jnp.int32)

    def pack_pool(a):  # (1, kvh, P, ps, hd) -> (1, G, P, ps, pack * hd)
        return a.reshape(1, groups, pack, P, ps, hd) \
            .transpose(0, 1, 3, 4, 2, 5).reshape(1, groups, P, ps, pack * hd)

    packed = [pack_pool(a) for a in unpacked]
    wrote_u = kernels.op("page_write")(
        *unpacked, new, new, ids, rows, 0, policy="pallas")
    wrote_p = kernels.op("page_write")(
        *packed, *[new.reshape(b, s, groups, pack * hd)] * 2, ids, rows, 0,
        policy="pallas")
    for u, p in zip(wrote_u, wrote_p):
        np.testing.assert_array_equal(np.asarray(pack_pool(u)), np.asarray(p))

    q = jax.random.normal(ks[3], (b, s, h, hd), jnp.float32)
    table = jnp.array([[1, 2, 3, 4], [5, 6, 0, 0]], jnp.int32)
    lengths = jnp.array([32, 11], jnp.int32)
    start = lengths - s
    want = paged_attention_ref(q, *wrote_u, table, start, lengths, 0)
    np.testing.assert_array_equal(
        np.asarray(paged_attention_ref(q, *wrote_p, table, start, lengths, 0)),
        np.asarray(want))
    pre = paged_attention_prefill(q, *wrote_p, table, start, lengths, 0,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    dec = paged_attention_decode(q[:, -1], *wrote_p, table, lengths - 1,
                                 lengths, 0, interpret=True)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(want[:, -1]),
                               rtol=1e-5, atol=1e-5)


class _Forced:
    """A registry op whose every call is forced to one policy."""

    def __init__(self, op, policy):
        self.op, self.policy = op, policy

    def __call__(self, *arrays, **opts):
        return self.op(*arrays, policy=self.policy, **opts)

    def __getattr__(self, name):
        return getattr(self.op, name)


def _model_steps(cfg, params, kv):
    """A cold prefill into pages, a 6-token suffix chunk across a page
    boundary (bucket-padded to 8) and a decode step over three rows (one
    idle), through the model's own entry points: (logits, [pool after
    the cold prefills, pool at the end])."""
    from repro.models import lm
    ps, width = 8, 8
    caches = lm.init_paged_cache(cfg, 20, ps, kv)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (3, 24)), jnp.int32)
    table = np.zeros((3, width), np.int32)
    table[0, :3], table[1, :2] = [1, 2, 3], [4, 5]
    table = jnp.asarray(table)
    out = []
    for row, n in ((0, 13), (1, 5)):
        padded = jnp.zeros((1, 16), jnp.int32).at[0, :n].set(toks[row, :n])
        logits, dense = lm.prefill(params, cfg, padded, logit_index=n - 1)
        caches = lm.prefill_to_pages(dense, caches, table[row], n)
        out.append(logits)
    pools = [caches]
    chunk = jnp.zeros((1, 8), jnp.int32).at[0, :6].set(toks[1, 5:11])
    logits, caches = lm.decode_step(
        params, cfg, caches, chunk, jnp.int32(5), block_table=table[1:2],
        lengths=jnp.array([11], jnp.int32))
    out.append(logits[:, :6])
    logits, caches = lm.decode_step(
        params, cfg, caches, toks[:, 20:21], jnp.array([13, 11, 0], jnp.int32),
        block_table=table, lengths=jnp.array([14, 12, 0], jnp.int32))
    out.append(logits[:2])
    return out, pools + [caches]


@pytest.mark.parametrize("kv,attn_cfg", [
    ("bf16", None),  # the reduced model's own heads: one per row
    ("bf16", AttnConfig(n_heads=4, n_kv_heads=2, head_dim=64, qkv_bias=True)),
    ("bf16", AttnConfig(n_heads=8, n_kv_heads=8, head_dim=32, qkv_bias=True)),
    ("int8", None),
], ids=["bf16", "bf16_packed2", "bf16_packed4_groups2", "int8"])
def test_model_steps_with_kernels_match_reference(monkeypatch, kv, attn_cfg):
    """Cold prefill into pages, a suffix chunk and a decode step through
    the model with the Pallas page write and attention kernels
    (interpret mode) against the reference path, for bf16 pools one head
    or several to a lane row and for the int8 pool.  The cold prefill's
    pages match bit for bit in every layer, and layer 0's (whose rows
    depend on no attention output) after every step.  Logits and later
    layers' rows agree to the bf16 rounding by which the kernels and the
    reference differ (chip_smoke's per-kernel measure)."""
    import dataclasses

    from repro.configs import get_config
    from repro.kernels import api
    from repro.models import lm

    cfg = get_config("qwen1.5-0.5b", reduced=True)
    if attn_cfg is not None:
        cfg = dataclasses.replace(cfg, attn=attn_cfg)
    params = lm.init(cfg, jax.random.PRNGKey(1))
    want, (want_cold, want_end) = _model_steps(cfg, params, kv)
    for name in ("paged_attention", "page_write"):
        monkeypatch.setitem(api._REGISTRY, name,
                            _Forced(api.op(name), "pallas"))
    got, (got_cold, got_end) = _model_steps(cfg, params, kv)

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return np.abs(a - b).max() / np.abs(b).max()

    for g, w in zip(got, want):
        assert rel(g, w) <= 2**-4
    for g, w in zip(jax.tree.leaves(got_cold), jax.tree.leaves(want_cold)):
        np.testing.assert_array_equal(np.asarray(g[:, :, 1:]),
                                      np.asarray(w[:, :, 1:]))
    for g, w in zip(jax.tree.leaves(got_end), jax.tree.leaves(want_end)):
        np.testing.assert_array_equal(np.asarray(g[0, :, 1:]),
                                      np.asarray(w[0, :, 1:]))
        if kv == "bf16":
            assert rel(g[:, :, 1:], w[:, :, 1:]) <= 2**-4
