"""Expert-parallel switch MoE (all_to_all dispatch) vs a dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _ep_mesh(ep):
    from kungfu_tpu.parallel import make_mesh

    return make_mesh({"ep": ep}, devices=jax.devices()[:ep])


def _dense_reference(x_all, router_w, w_in_all, w_out_all):
    """Every token through its argmax expert, gate-scaled (no drops) —
    the top_k=1 case of _dense_topk_reference."""
    return _dense_topk_reference(x_all, router_w, w_in_all, w_out_all, 1)


def _run_moe(x, router_w, w_in_all, w_out_all, ep, capacity_factor):
    from kungfu_tpu.ops.moe import switch_moe

    mesh = _ep_mesh(ep)

    def shard_fn(x_sh, router_w, w_in_sh, w_out_sh):
        # w_*_sh arrive with a leading (1,) expert-shard axis
        return switch_moe(
            x_sh, router_w, w_in_sh[0], w_out_sh[0], "ep", ep,
            capacity_factor=capacity_factor,
        )

    fn = jax.jit(
        shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P("ep"), P(), P("ep"), P("ep")),
            out_specs=(P("ep"), P()),
            check_vma=False,
        )
    )
    return fn(x, router_w, w_in_all, w_out_all)


def test_switch_moe_matches_dense_when_no_drops():
    ep, T, D, F = 4, 32, 8, 16
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (T, D), jnp.float32)
    router_w = jax.random.normal(jax.random.PRNGKey(1), (D, ep), jnp.float32)
    w_in = jax.random.normal(jax.random.PRNGKey(2), (ep, D, F), jnp.float32) * 0.3
    w_out = jax.random.normal(jax.random.PRNGKey(3), (ep, F, D), jnp.float32) * 0.3

    # capacity_factor=ep: even if one shard routes ALL its tokens to one
    # expert, nothing drops
    out, aux = _run_moe(x, router_w, w_in, w_out, ep, capacity_factor=float(ep))
    ref = _dense_reference(np.asarray(x), router_w, w_in, w_out)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)
    assert np.isfinite(float(aux)) and float(aux) > 0


def test_switch_moe_capacity_drops_are_zero():
    ep, T, D, F = 4, 32, 8, 16
    x = jax.random.normal(jax.random.PRNGKey(5), (T, D), jnp.float32)
    router_w = jnp.zeros((D, ep), jnp.float32)  # uniform router: argmax=0
    w_in = jnp.ones((ep, D, F), jnp.float32)
    w_out = jnp.ones((ep, F, D), jnp.float32)
    # everyone routes to expert 0; tiny capacity -> most tokens dropped
    out, _ = _run_moe(x, router_w, w_in, w_out, ep, capacity_factor=0.5)
    out = np.asarray(out)
    per_shard = T // ep
    C = max(1, int(0.5 * per_shard / ep))
    nonzero_rows = (np.abs(out).sum(-1) > 0).reshape(ep, per_shard).sum(1)
    assert (nonzero_rows <= C).all(), (nonzero_rows, C)


def test_switch_moe_differentiable():
    ep, T, D, F = 4, 16, 4, 8
    x = jax.random.normal(jax.random.PRNGKey(9), (T, D), jnp.float32)
    router_w = jax.random.normal(jax.random.PRNGKey(10), (D, ep), jnp.float32)
    w_in = jax.random.normal(jax.random.PRNGKey(11), (ep, D, F), jnp.float32)
    w_out = jax.random.normal(jax.random.PRNGKey(12), (ep, F, D), jnp.float32)
    mesh = _ep_mesh(ep)

    from kungfu_tpu.ops.moe import switch_moe

    def loss(params, x):
        rw, wi, wo = params

        def shard_fn(x_sh, rw, wi_sh, wo_sh):
            out, aux = switch_moe(x_sh, rw, wi_sh[0], wo_sh[0], "ep", ep, 2.0)
            return jax.lax.pmean(jnp.mean(out**2), "ep") + 0.01 * aux

        return shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P("ep"), P(), P("ep"), P("ep")),
            out_specs=P(),
            check_vma=False,
        )(x, rw, wi, wo)

    g = jax.jit(jax.grad(loss))((router_w, w_in, w_out), x)
    for leaf in jax.tree.leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()
    # expert weights receive gradient (tokens actually flowed through)
    assert float(jnp.abs(g[1]).sum()) > 0
    assert float(jnp.abs(g[0]).sum()) > 0  # router learns via the gate


def _dense_topk_reference(x_all, router_w, w_in_all, w_out_all, top_k,
                          renormalize=True):
    """Every token through its top-k experts, renormalized gates (or the
    raw probabilities), no drops (numpy reference for moe_ffn)."""
    logits = x_all.astype(np.float32) @ np.asarray(router_w, np.float32)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=-1)[:, :top_k]
    out = np.zeros_like(x_all, dtype=np.float32)
    for i in range(len(x_all)):
        chosen = order[i]
        g = probs[i, chosen]
        if top_k > 1 and renormalize:
            g = g / g.sum()
        for ei, gi in zip(chosen, g):
            h = jax.nn.gelu(
                x_all[i].astype(np.float32) @ np.asarray(w_in_all[ei], np.float32)
            )
            out[i] += (np.asarray(h) @ np.asarray(w_out_all[ei], np.float32)) * gi
    return out


def _run_moe_general(x, router_w, w_in_all, w_out_all, ep, top_k,
                     capacity_factor):
    from kungfu_tpu.ops.moe import moe_ffn

    mesh = _ep_mesh(ep)

    def shard_fn(x_sh, router_w, w_in_sh, w_out_sh):
        # w_*_sh arrive with a leading (1,) shard axis over the (epd, ...)
        # expert stack
        out, aux = moe_ffn(
            x_sh, router_w, (w_in_sh[0], w_out_sh[0]), "ep", ep,
            top_k=top_k, capacity_factor=capacity_factor,
        )
        return out, aux.load_balance, aux.counts

    fn = jax.jit(
        shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P("ep"), P(), P("ep"), P("ep")),
            out_specs=(P("ep"), P(), P("ep")),
            check_vma=False,
        )
    )
    return fn(x, router_w, w_in_all, w_out_all)


def _weights(E, D, F):
    router_w = jax.random.normal(jax.random.PRNGKey(1), (D, E), jnp.float32)
    w_in = jax.random.normal(jax.random.PRNGKey(2), (E, D, F), jnp.float32) * 0.3
    w_out = jax.random.normal(jax.random.PRNGKey(3), (E, F, D), jnp.float32) * 0.3
    return router_w, w_in, w_out


# (top_k, shards, experts per shard): today's top-1 and top-2 cases, and
# OLMoE's eight
WIRE_CASES = [(2, 4, 1), (1, 4, 2), (8, 4, 4)]


@pytest.mark.parametrize("top_k,ep,epd", WIRE_CASES)
def test_moe_matches_dense_when_no_drops(top_k, ep, epd):
    T, D, F = 32, 8, 16
    E = ep * epd
    x = jax.random.normal(jax.random.PRNGKey(0), (T, D), jnp.float32)
    router_w, w_in, w_out = _weights(E, D, F)
    out, aux, counts = _run_moe_general(
        x, router_w, w_in.reshape(ep, epd, D, F), w_out.reshape(ep, epd, F, D),
        ep, top_k=top_k, capacity_factor=float(E),
    )
    ref = _dense_topk_reference(np.asarray(x), router_w, w_in, w_out, top_k)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux)) and float(aux) > 0
    # every shard's counts: all of its T / ep * top_k token-choices kept
    assert np.asarray(counts).reshape(ep, E).sum(1).tolist() == [T // ep * top_k] * ep


@pytest.mark.parametrize("top_k", [1, 2, 8])
def test_moe_one_shard_is_dropless_and_matches_dense(top_k):
    """`axis_size` 1: no capacity, no wire; every token-choice is computed
    whatever the load, here with every token sent to the same experts."""
    from kungfu_tpu.ops.moe import moe_ffn, raw_gates, switch_gates

    T, D, F, E = 48, 8, 16, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (T, D), jnp.float32)
    router_w, w_in, w_out = _weights(E, D, F)
    for gates, renormalize in ((switch_gates, True), (raw_gates, False)):
        out, aux = jax.jit(lambda x, r: moe_ffn(
            x, r, (w_in, w_out), top_k=top_k, gates=gates))(x, router_w)
        ref = _dense_topk_reference(np.asarray(x), router_w, w_in, w_out,
                                    top_k, renormalize)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)
        assert int(aux.counts.sum()) == T * top_k
        assert aux.chosen.shape == (T, top_k)
    # an adversarial router: the first top_k experts take every token
    # (a constant feature, so the logits do not depend on the token)
    x1 = x.at[:, 0].set(1.0)
    skewed = jnp.zeros((D, E)).at[0, :top_k].set(
        20.0 + jnp.arange(top_k, 0, -1.0))
    out, aux = jax.jit(lambda x, r: moe_ffn(
        x, r, (w_in, w_out), top_k=top_k))(x1, skewed)
    assert aux.counts.tolist() == [T] * top_k + [0] * (E - top_k)
    ref = _dense_topk_reference(np.asarray(x1), skewed, w_in, w_out, top_k)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)
    # uniform load balances to 1, everything on top_k of E experts to E / top_k
    assert float(aux.load_balance) == pytest.approx(E / top_k, rel=1e-3)


def test_moe_refuses_what_it_cannot_route():
    from kungfu_tpu.ops.moe import moe_ffn

    x = jnp.zeros((4, 8))
    router_w, w_in, w_out = _weights(4, 8, 16)
    with pytest.raises(ValueError, match="at least 1"):
        moe_ffn(x, router_w, (w_in, w_out), top_k=0)
    with pytest.raises(ValueError, match="exceeds the 4 experts"):
        moe_ffn(x, router_w, (w_in, w_out), top_k=5)
    with pytest.raises(ValueError, match="router width"):
        moe_ffn(x, router_w[:, :3], (w_in, w_out))


@pytest.mark.parametrize("top_k,ep", [(2, 2), (8, 1)])
def test_moe_differentiable(top_k, ep):
    T, D, F = 16, 8, 8
    E = max(ep, top_k)
    epd = E // ep
    from kungfu_tpu.ops.moe import moe_ffn

    mesh = _ep_mesh(ep)
    x = jax.random.normal(jax.random.PRNGKey(0), (T, D), jnp.float32)
    router_w, w_in, w_out = _weights(E, D, F)
    w_in, w_out = w_in.reshape(ep, epd, D, F), w_out.reshape(ep, epd, F, D)

    def loss(params):
        w_in, w_out, router_w = params

        def shard_fn(x_sh, router_w, w_in_sh, w_out_sh):
            out, aux = moe_ffn(x_sh, router_w, (w_in_sh[0], w_out_sh[0]),
                               "ep", ep, top_k=top_k, capacity_factor=2.0)
            return (jax.lax.pmean(jnp.sum(out ** 2), "ep")
                    + 0.01 * aux.load_balance + 0.001 * aux.z_loss)

        fn = shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P("ep"), P(), P("ep"), P("ep")),
            out_specs=P(),
            check_vma=False,
        )
        return fn(x, router_w, w_in, w_out)

    g = jax.jit(jax.grad(loss))((w_in, w_out, router_w))
    for t in g:
        assert np.all(np.isfinite(np.asarray(t)))
    assert float(np.abs(np.asarray(g[0])).sum()) > 0
    assert float(np.abs(np.asarray(g[2])).sum()) > 0


# --- a share of the experts on one shard (PR 33) ------------------------------

def _share_setup(T=48, D=16, F=8, E=16, seed=0):
    from kungfu_tpu.ops import moe

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, D), jnp.float32)
    router = jax.random.normal(ks[1], (D, E), jnp.float32)
    experts = tuple(jax.random.normal(k, shape, jnp.float32) * 0.3
                    for k, shape in zip(ks[2:], ((E, D, F), (E, D, F), (E, F, D))))
    gates = moe.scaled(moe.renormalised_gates, 2.5)
    return moe, x, router, experts, gates


def _share(moe, x, router, experts, gates, top_k, first, count):
    mine = tuple(w[first:first + count] for w in experts)
    return moe.moe_ffn(x, router, mine, top_k=top_k, gates=gates,
                       expert_fn=moe.swiglu_experts, held=(first, count))


@pytest.fixture
def row_kernels(request):
    """Whether an unbiased share's rows are moved by `ops/row_moves.py`'s
    kernels, interpreted, on row tiles of 8 and one strip (the tests' 72, 40
    and 144 rows of 16 columns tile nowhere by the rule and take XLA's gather
    and scatter-add): the same values and gradients either way, a case's last
    parameter (`indirect`; an interpreted program is 2 s a share, so some
    cases and not all). No trace made under the patches outlives the test."""
    if not request.param:
        yield False
        return
    from jaxprs import interpret_kernels
    from kungfu_tpu.ops import row_moves as rm

    jax.clear_caches()
    with pytest.MonkeyPatch.context() as m:
        interpret_kernels(m, rm, ("_take", "_add"))
        m.setattr(rm, "tiling", lambda N, D, T: rm.Tiles(8, D))
        yield True
    jax.clear_caches()


@pytest.mark.parametrize("top_k,count,E,row_kernels", [
    (3, 4, 16, False), (5, 2, 16, False), (1, 8, 16, False), (3, 16, 16, False),
    (3, 4, 64, False), (3, 4, 16, True)], indirect=["row_kernels"])
def test_the_shares_parts_add_up_to_the_whole_layer(top_k, count, E, row_kernels):
    """Every share routes over all E experts and computes its own experts'
    part: the parts of all the shares sum to the layer with every expert
    held, values and gradients, and the shares' counts are the whole
    layer's, side by side."""
    moe, x, router, experts, gates = _share_setup(E=E)

    def whole(x, router, experts):
        return moe.moe_ffn(x, router, experts, top_k=top_k, gates=gates,
                           expert_fn=moe.swiglu_experts)

    def parts(x, router, experts):
        outs = [_share(moe, x, router, experts, gates, top_k, first, count)
                for first in range(0, E, count)]
        return (sum(out for out, _ in outs),
                jnp.concatenate([aux.counts for _, aux in outs]))

    want, want_aux = whole(x, router, experts)
    got, counts = parts(x, router, experts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert counts.tolist() == want_aux.counts.tolist()
    assert int(counts.sum()) == 48 * top_k

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a)[0] ** 2)

    grads = lambda fn: jax.tree.leaves(
        jax.jit(jax.grad(loss(fn), (0, 1, 2)))(x, router, experts))
    for g, w in zip(grads(parts), grads(whole)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("E,first,aimed,chunks,row_kernels", [
    (16, 4, 144, 2, False), (32, 4, 144, 2, False), (64, 4, 144, 4, False),
    (32, 8, 144, 0, False), (16, 8, 144, 0, False), (16, 4, 0, 0, False),
    (16, 4, 72, 1, False), (16, 4, 73, 2, False),
    # the kernels: four chunks to the last row, one row in a second chunk, none
    (64, 4, 144, 4, True), (16, 4, 73, 2, True), (16, 4, 0, 0, True)],
    indirect=["row_kernels"])
def test_a_share_is_dropless_under_the_worst_load(E, first, aimed, chunks,
                                                  row_kernels):
    """`aimed` of the 144 token-choices on experts 4, 5 and 6: the first
    tokens send all three there, one more token its first where `aimed` is
    no multiple of three, and the rest go to experts 0, 1 and 2. The four
    experts held from 4 on get them in as many chunks as they fill, chunks
    of four balanced loads and never more than half of all T x min(top_k,
    held) rows that can fall here (of 16 and of 32 experts 72 rows, of 64
    40): none where nothing came, one for exactly a chunk's rows, two for
    one row more and for every row that can fall here (of 64 experts four),
    and none is dropped. Values and gradients are those of one chunk of all
    the rows and, where every choice came, the whole layer's. The four held
    from 8 on get every choice elsewhere: no chunk runs, and the part and
    its gradients are exact zeros, as where nothing was aimed."""
    moe, x, router, experts, gates = _share_setup(E=E)
    chunk = moe._share_chunk(48, 3, 4, E)
    # logits that put experts 4, 5, 6 first for a token of kind 0, expert 4
    # and then 0 and 1 for one of kind 1 and 0, 1, 2 for one of kind 2,
    # whatever the rest of x
    kind = np.full(48, 2)
    kind[:aimed // 3] = 0
    kind[aimed // 3:aimed // 3 + aimed % 3] = 1
    router = (jnp.zeros_like(router).at[0, 4:7].set(jnp.array([3.0, 2.0, 1.0]))
              .at[1, jnp.array([4, 0, 1])].set(jnp.array([3.0, 2.0, 1.0]))
              .at[2, 0:3].set(jnp.array([3.0, 2.0, 1.0])))
    x = x.at[:, :3].set(jax.nn.one_hot(kind, 3) * (jnp.abs(x[:, :1]) + 1.0))
    out, aux = _share(moe, x, router, experts, gates, 3, first, 4)
    assert int(moe._live_chunks(3, chunk, 48, aux.counts)) == chunks

    def whole(x, router, experts):
        return moe.moe_ffn(x, router, experts, top_k=3, gates=gates,
                           expert_fn=moe.swiglu_experts)[0]

    def part(x, router, experts):
        return _share(moe, x, router, experts, gates, 3, first, 4)[0]

    def one_chunk(x, router, experts):
        """The same share as one chunk of all 144 rows that can fall here."""
        top_scores, chosen = moe.route(x, router, 3)[2:]
        order, _, counts = moe.dispatch_plan(chosen, E, (first, 4))
        return moe._held_part(
            moe.swiglu_experts, 3, 144, False, x, gates(top_scores),
            tuple(w[first:first + 4] for w in experts), order,
            counts[first:first + 4])

    grads = lambda fn: jax.tree.leaves(jax.grad(
        lambda *a: jnp.sum(fn(*a) ** 2), (0, 1, 2))(x, router, experts))
    if first == 8 or not aimed:
        assert aux.counts.tolist() == [0, 0, 0, 0]
        assert float(jnp.abs(out).max()) == 0.0
        assert all(float(jnp.abs(g).max()) == 0.0 for g in grads(part))
        return
    assert aux.counts.tolist() == [-(-aimed // 3), aimed // 3, aimed // 3, 0]
    assert int(aux.counts.sum()) == aimed  # every held choice computed
    got = grads(part)
    for want in (one_chunk, whole) if aimed == 144 else (one_chunk,):
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(want(x, router, experts)),
                                   rtol=1e-5, atol=1e-5)
        for g, w in zip(got, grads(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("biased", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("E,one", [(16, True), (32, True), (64, False)])
def test_a_chunk_of_all_that_can_fall_here_does_not_follow_the_routing(E, one,
                                                                       biased):
    """A share's work is its live rows' unless a selection bias says
    otherwise. Under a bias, where two usual chunks hold all that can fall
    on the held experts (a share of an eighth of them or more), one chunk
    holds it, and the groups handed to the experts fill every chunk
    whatever came, the rows of no group in the last one, where they are
    zeros and weigh nothing (the values and gradients of the tests above).
    Without a bias the chunk is four balanced loads and no more than half
    of what can fall here, and the groups are the rows that came, zeros
    past them. One chunk of all that can fall here, which is then the
    bias's alone, is run once, whatever came: the program has no loop whose
    count the routing gives; a smaller chunk keeps its loop."""
    moe, x, router, experts, gates = _share_setup(E=E)
    chunk = moe._share_chunk(48, 3, 4, E, biased)
    assert chunk == (48 * 3 if one and biased else
                     {16: 72, 32: 72, 64: 40}[E])
    mine = tuple(w[4:8] for w in experts)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (E,)) if biased else None

    def part(x, router):
        return moe.moe_ffn(x, router, mine, top_k=3, gates=gates,
                           expert_fn=moe.swiglu_experts, held=(4, 4),
                           bias=bias)[0]

    assert ("while" in str(jax.make_jaxpr(part)(x, router))) == (chunk < 48 * 3)
    seen = []

    def spy(rows, experts, sizes):
        seen.append((rows, sizes))
        return moe.swiglu_experts(rows, experts, sizes)

    order = jnp.arange(48 * 3, dtype=jnp.int32)
    for sizes in ([5, 0, 7, 2], [0, 0, 0, 0]):  # some rows; all elsewhere
        out = moe._chunk_part(spy, 3, chunk, x, jnp.ones((48, 3)), mine, order,
                              jnp.array(sizes, jnp.int32), 0, biased)
        rows, groups = seen.pop()
        live = sum(sizes)
        assert float(jnp.abs(rows[live:]).max()) == 0.0
        if biased:  # the rows of no group are the last group's
            assert groups.tolist() == sizes[:3] + [sizes[3] + chunk - live]
        else:
            assert groups.tolist() == sizes
        assert (live == 0) == (float(jnp.abs(out).max()) == 0.0)
        assert int(moe._live_chunks(3, chunk, 48, np.array(sizes))) == (
            1 if chunk == 48 * 3 else -(-live // chunk))


@pytest.mark.parametrize("biased", [False, True], ids=["no_bias", "bias"])
def test_a_whole_chunk_holds_no_kernel_of_the_rows_movement(biased):
    """At a shape `ops/row_moves.tiling` takes (chunks of 128 and 256 rows
    of 128 columns for 64 tokens) a share without a bias stages the rows'
    kernels for the TPU in both passes, `take_rows` twice (the dispatch, and
    again in the backward pass's loop), `add_rows` three times (the combine,
    again in that loop, where nothing reads it and XLA drops it, and the
    dispatch's transpose) and `take_rows_weighted` (the combine's
    transpose); under a selection bias the chunk runs `whole`, on the path
    it had: XLA's gather and scatter-add, and no kernel of that module."""
    from kungfu_tpu.ops import row_moves

    moe, x, router, experts, gates = _share_setup(T=64, D=128, F=128)
    chunk = moe._share_chunk(64, 4, 4, 16, biased)
    assert chunk == (256 if biased else 128)
    assert row_moves.tiling(chunk, 128, 64) is not None
    mine = tuple(w[4:8] for w in experts)
    bias = jnp.linspace(-0.1, 0.1, 16) if biased else None

    def loss(x, router, mine):
        return jnp.sum(moe.moe_ffn(x, router, mine, top_k=4, gates=gates,
                                   expert_fn=moe.swiglu_experts, held=(4, 4),
                                   bias=bias)[0] ** 2)

    eqns = list(_all_eqns(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
        x, router, mine).jaxpr))
    kernels = sorted(eqn.params["name"] for eqn in eqns
                     if eqn.primitive.name == "pallas_call")
    if biased:
        assert kernels == []
    else:
        assert kernels == ["add_rows"] * 3 + ["take_rows"] * 2 + [
            "take_rows_weighted"]
    # XLA's forms: the path itself under a bias, the other platforms' branch
    # beside the kernels
    moved = [eqn.primitive.name for eqn in eqns
             if eqn.primitive.name in ("gather", "scatter-add")
             and 128 in eqn.outvars[0].aval.shape]
    assert {"gather", "scatter-add"} <= set(moved)


# what `_share_chunk` gives at the cells' shapes: T, top_k, held, experts, bias
CELL_CHUNKS = {
    "glm_4_7_flash": ((8192, 4, 8, 64, True), 32768),
    "lfm2_24b_a2b": ((8192, 4, 8, 64, True), 32768),
    "nemotron_3_nano_30b_a3b": ((8192, 6, 8, 128, True), 12288),
    "laguna_s_2_1": ((8192, 10, 8, 256, False), 10240),
    "qwen3_next_80b_a3b": ((16384, 10, 32, 512, False), 40960),
    "keye_vl_2_0_30b_a3b": ((8192, 8, 16, 128, False), 32768),
    "smallthinker_21b_a3b": ((16384, 6, 16, 64, False), 49152),
}


@pytest.mark.parametrize("cell", CELL_CHUNKS)
def test_the_one_chunk_rule_is_the_selection_biass(cell):
    """Under a bias a share's chunk is what it was at PR 65, one chunk of all
    that can fall here for a share of an eighth or more (GLM-4.7-Flash,
    LFM2) and the usual chunk below that (Nemotron-3-Nano); without one it
    is four balanced loads and never more than half of what can fall here:
    half of the 65,536 rows in the Keye cell either way, half of the 98,304
    in the SmallThinker cell, whose four balanced loads are all of them
    (PR 68), and four balanced loads for the smaller shares (Laguna,
    Qwen3-Next)."""
    from kungfu_tpu.ops import moe

    (T, top_k, held, E, biased), rows = CELL_CHUNKS[cell]
    assert moe._share_chunk(T, top_k, held, E, biased) == rows
    most, balanced = T * min(top_k, held), T * top_k * held // E
    assert rows == ((most if most <= 8 * balanced else 4 * balanced) if biased
                    else min(most // 2, 4 * balanced))


def test_the_chunk_fill_share_is_the_held_rows_over_the_chunks_that_ran():
    """`kungfu_moe_chunk_fill_share` against a count by hand: a model of two
    expert layers that holds experts 4 to 7 of 64 (chunks of 48 rows for 64
    tokens of 3 choices), the rows of its chunks that ran from its own
    choices; then the gauge of stats written by hand, a layer of two chunks,
    one of none and one under a bias's one chunk."""
    from kungfu_tpu.models import transformer
    from kungfu_tpu.models.transformer import TransformerConfig
    from kungfu_tpu.telemetry import metrics

    cfg = TransformerConfig.tiny_moe(n_experts=64, experts_held=(4, 4))
    params = transformer.init_transformer(jax.random.PRNGKey(2), cfg)
    params["layers"]["router"] = params["layers"]["router"].at[1, :, 4:8].multiply(50.0)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, cfg.vocab_size)
    stats = jax.jit(lambda p, t: transformer.routing_stats(p, t, cfg))(params, tokens)
    chosen = np.asarray(stats["chosen"])
    rows = ((chosen >= 4) & (chosen < 8)).sum(axis=(1, 2))
    assert rows.tolist() == stats["held_rows"].tolist() and rows[1] > 48 > rows[0] > 0
    assert stats["chunk_rows"].tolist() == (48 * -(-rows // 48)).tolist()
    # chunks of 48 rows of 32 columns tile nowhere: XLA's forms move them all
    assert stats["rows_moved"].tolist() == stats["chunk_rows"].tolist()
    registry = metrics.Registry()
    transformer.record_routing(stats, registry)
    text = registry.render()
    for layer in range(2):
        share = rows[layer] / (48 * -(-rows[layer] // 48))
        line = [l for l in text.splitlines() if l.startswith(
            f'kungfu_moe_chunk_fill_share{{layer="{layer}"}}')]
        assert float(line[0].split()[-1]) == pytest.approx(share, rel=1e-6)
        assert f'kungfu_moe_rows_moved_share{{layer="{layer}"}} 1' in text
    by_hand = {"counts": np.array([[30, 20, 10, 10], [0, 0, 0, 0], [5, 0, 7, 2]]),
               "held_rows": np.array([70, 0, 14]), "dropped": np.zeros(3),
               "max_over_mean": np.ones(3), "chosen": np.zeros((3, 64, 3), np.int32),
               "layer": np.array([0, 2, 5]), "chunk_rows": np.array([96, 0, 192]),
               "rows_moved": np.array([80, 0, 192])}
    registry = metrics.Registry()
    transformer.record_routing(by_hand, registry)
    text = registry.render()
    assert f'kungfu_moe_chunk_fill_share{{layer="0"}} {70 / 96}' in text
    assert 'kungfu_moe_chunk_fill_share{layer="2"} 1' in text  # no chunk ran
    assert f'kungfu_moe_chunk_fill_share{{layer="5"}} {14 / 192}' in text
    # the rows of the tiles the movement visits over the same chunks: the
    # fill rounded up to a row tile, 1 where no chunk ran and under a bias
    assert f'kungfu_moe_rows_moved_share{{layer="0"}} {80 / 96}' in text
    assert 'kungfu_moe_rows_moved_share{layer="2"} 1' in text
    assert 'kungfu_moe_rows_moved_share{layer="5"} 1' in text
    whole = TransformerConfig.tiny_moe()  # every expert held: no chunks, no gauge
    stats = jax.jit(lambda p, t: transformer.routing_stats(p, t, whole))(
        transformer.init_transformer(jax.random.PRNGKey(2), whole), tokens)
    registry = metrics.Registry()
    transformer.record_routing(stats, registry)
    assert "chunk_rows" not in stats and "chunk_fill_share" not in registry.render()
    assert "rows_moved" not in stats and "rows_moved_share" not in registry.render()


def test_a_share_of_every_expert_is_the_layer_itself():
    """`held` = (0, E) lowers to the program without it, text for text."""
    moe, x, router, experts, gates = _share_setup()

    def run(held):
        return jax.jit(lambda x, r, e: moe.moe_ffn(
            x, r, e, top_k=3, gates=moe.raw_gates,
            expert_fn=moe.swiglu_experts, held=held)[0]).lower(
                x, router, experts).as_text()

    assert run((0, 16)) == run(None)


def test_a_share_that_does_not_fit_raises():
    moe, x, router, experts, gates = _share_setup()
    mine = tuple(w[:4] for w in experts)
    for held in ((0, 3), (13, 4), (-1, 4)):
        with pytest.raises(ValueError, match="held"):
            moe.moe_ffn(x, router, mine, top_k=2, gates=gates,
                        expert_fn=moe.swiglu_experts, held=held)
    with pytest.raises(ValueError, match="held"):
        moe.moe_ffn(x, router, mine, "ep", 2, top_k=2, gates=gates,
                    expert_fn=moe.swiglu_experts, held=(0, 4))


def test_gate_rules():
    from kungfu_tpu.ops import moe

    p = jnp.array([[0.5, 0.25, 0.05], [0.2, 0.2, 0.1]])
    np.testing.assert_allclose(moe.renormalised_gates(p).sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(moe.renormalised_gates(p[:, :1]), 1.0)  # top-1 too
    assert moe.scaled(moe.raw_gates, 1.0) is moe.raw_gates
    np.testing.assert_allclose(moe.scaled(moe.raw_gates, 2.5)(p), 2.5 * p)


# --- the router's rule (PR 41): softmax or sigmoid scores, a selection bias --

@pytest.mark.parametrize("rule", ["softmax", "sigmoid"])
def test_route_scores_by_its_rule(rule):
    from kungfu_tpu.ops import moe

    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x, router = jax.random.normal(ks[0], (40, 16)), jax.random.normal(ks[1], (16, 8))
    logits, scores, top, idx = moe.route(x, router, 3, rule)
    np.testing.assert_allclose(logits, x @ router, rtol=1e-5, atol=1e-5)
    want = (jax.nn.softmax if rule == "softmax" else jax.nn.sigmoid)(logits)
    np.testing.assert_array_equal(scores, want)
    np.testing.assert_array_equal(top, jnp.take_along_axis(scores, idx, -1))
    np.testing.assert_array_equal(idx, jnp.argsort(-scores, axis=-1)[:, :3])
    # a softmax's scores sum to one over the experts; a sigmoid's are each
    # expert's own
    assert np.allclose(scores.sum(-1), 1.0) == (rule == "softmax")
    if rule == "softmax":  # the rule the function always had, by default
        for got, was in zip(moe.route(x, router, 3), (logits, scores, top, idx)):
            np.testing.assert_array_equal(got, was)
    with pytest.raises(ValueError, match="scores"):
        moe.route(x, router, 3, "relu")


@pytest.mark.parametrize("rule", ["softmax", "sigmoid"])
def test_route_chooses_on_scores_plus_bias_and_weighs_by_the_scores(rule):
    from kungfu_tpu.ops import moe

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x, router = jax.random.normal(ks[0], (64, 16)), 0.3 * jax.random.normal(ks[1], (16, 8))
    bias = jnp.zeros(8).at[5].set(10.0)  # expert 5 wins every choice
    _, scores, top, idx = moe.route(x, router, 2, rule, bias)
    assert (idx[:, 0] == 5).all()
    np.testing.assert_array_equal(top, jnp.take_along_axis(scores, idx, -1))
    assert float(top.max()) <= 1.0  # the bias is in no weight
    _, _, plain_top, plain_idx = moe.route(x, router, 2, rule)
    # the second choice is the plain first, unless that was expert 5
    first = np.asarray(plain_idx[:, 0])
    second = np.where(first == 5, np.asarray(plain_idx[:, 1]), first)
    np.testing.assert_array_equal(idx[:, 1], second)
    # a bias of zero moves nothing; the count is of the choices it changed
    _, _, _, same = moe.route(x, router, 2, rule, jnp.zeros(8))
    np.testing.assert_array_equal(same, plain_idx)
    assert int(moe.bias_moved(scores, same)) == 0
    changed = sum(5 not in row for row in np.asarray(plain_idx).tolist())
    assert int(moe.bias_moved(scores, idx)) == changed > 0


@pytest.mark.parametrize("held", [None, (4, 4)])
def test_moe_ffn_under_sigmoid_scores_and_a_bias_matches_dense(held):
    """The layer under the GLM-4.7-Flash rule against every expert run over
    every token and weighed by hand: the whole layer, and one share."""
    moe, x, router, experts, _ = _share_setup(E=16)
    gates = moe.scaled(moe.renormalised_gates, 1.8)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    mine = experts if held is None else tuple(w[4:8] for w in experts)
    got, aux = moe.moe_ffn(x, router, mine, top_k=3, gates=gates,
                           expert_fn=moe.swiglu_experts, held=held,
                           scores="sigmoid", bias=bias)
    scores = jax.nn.sigmoid(x @ router)
    idx = jnp.argsort(-(scores + bias), axis=-1)[:, :3]
    top = jnp.take_along_axis(scores, idx, -1)
    weights = 1.8 * top / top.sum(-1, keepdims=True)
    first, count = held or (0, 16)
    want = jnp.zeros_like(x)
    for e in range(first, first + count):
        w_gate, w_up, w_down = (w[e] for w in experts)
        y = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
        want = want + jnp.sum(jnp.where(idx == e, weights, 0.0), -1)[:, None] * y
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert aux.counts.tolist() == np.bincount(
        np.asarray(idx).ravel(), minlength=16)[first:first + count].tolist()
    assert int(aux.bias_moved) == int(moe.bias_moved(scores, idx)) > 0
    # without a bias the aux says nothing of one
    plain = moe.moe_ffn(x, router, mine, top_k=3, gates=gates,
                        expert_fn=moe.swiglu_experts, held=held,
                        scores="sigmoid")[1]
    assert plain.bias_moved is None


# --- two-matrix relu^2 experts (PR 43) ----------------------------------------

def test_relu2_experts_are_two_matrices_over_the_groups():
    """`relu2_experts`: rows in groups, one group an expert, y = w_down
    (relu(w_up x))^2 with no gate matrix, values and every gradient against
    a loop over the experts; rows past the groups' sum give nothing."""
    from kungfu_tpu.ops import moe

    E, D, F = 4, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    sizes = jnp.asarray([5, 0, 7, 3], jnp.int32)
    rows = jax.random.normal(ks[0], (20, D))  # 15 in groups, 5 in none
    w_up = 0.5 * jax.random.normal(ks[1], (E, D, F))
    w_down = 0.5 * jax.random.normal(ks[2], (E, F, D))

    def by_hand(rows, w_up, w_down):
        out, at = [], 0
        for e, n in enumerate(sizes.tolist()):
            x = rows[at:at + n]
            out.append(jnp.square(jnp.maximum(x @ w_up[e], 0.0)) @ w_down[e])
            at += n
        return jnp.concatenate(out + [jnp.zeros((20 - at, D))])

    got = moe.relu2_experts(rows, (w_up, w_down), sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(by_hand(rows, w_up, w_down)),
                               rtol=1e-5, atol=1e-6)
    assert not np.asarray(got[15:]).any()
    weight = jax.random.normal(jax.random.PRNGKey(1), (20, D))
    grads = jax.grad(lambda *a: jnp.sum(moe.relu2_experts(a[0], a[1:], sizes) * weight),
                     (0, 1, 2))(rows, w_up, w_down)
    wants = jax.grad(lambda *a: jnp.sum(by_hand(*a) * weight), (0, 1, 2))(
        rows, w_up, w_down)
    for g, w in zip(grads, wants):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5)
    assert not np.asarray(grads[1][1]).any()  # the expert with no row
    # bfloat16 rows: the weights are met in the rows' type, the result is theirs
    low = moe.relu2_experts(rows.astype(jnp.bfloat16), (w_up, w_down), sizes)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(low, np.float32), np.asarray(got),
                               rtol=0.1, atol=0.05)


@pytest.mark.parametrize("held", [None, (4, 4), (8, 8)])
def test_moe_ffn_with_relu2_experts_matches_dense(held):
    """The layer under Nemotron-H's rule (sigmoid scores, a selection bias,
    the chosen renormalised and scaled by 2.5, two-matrix relu^2 experts)
    against every expert run over every token and weighed by hand: the whole
    layer, and a share of it."""
    moe, x, router, experts, gates = _share_setup(E=16)
    experts = experts[1:]  # (w_up, w_down): no gate matrix
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    first, count = held or (0, 16)
    mine = tuple(w[first:first + count] for w in experts)

    def layer(x, router, mine):
        return moe.moe_ffn(x, router, mine, top_k=6, gates=gates,
                           expert_fn=moe.relu2_experts, held=held,
                           scores="sigmoid", bias=bias)

    def by_hand(x, router, mine):
        scores = jax.nn.sigmoid(x @ router)
        idx = jnp.argsort(-(scores + bias), axis=-1)[:, :6]
        top = jnp.take_along_axis(scores, idx, -1)
        weights = 2.5 * top / top.sum(-1, keepdims=True)
        out = jnp.zeros_like(x)
        for e in range(count):
            y = jnp.square(jnp.maximum(x @ mine[0][e], 0.0)) @ mine[1][e]
            out = out + jnp.sum(jnp.where(idx == first + e, weights, 0.0),
                                -1)[:, None] * y
        return out, idx

    got, aux = layer(x, router, mine)
    want, idx = by_hand(x, router, mine)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert aux.counts.tolist() == np.bincount(
        np.asarray(idx).ravel(), minlength=16)[first:first + count].tolist()
    grads = jax.grad(lambda *a: jnp.sum(layer(*a)[0] ** 2), (0, 1, 2))(x, router, mine)
    wants = jax.grad(lambda *a: jnp.sum(by_hand(*a)[0] ** 2), (0, 1, 2))(x, router, mine)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(wants)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4, atol=1e-4)


def test_relu2_keeps_its_input_and_recomputes_the_square():
    """As `_silu_gate_down`: the backward pass keeps `up` and `w_down`, not
    the squared activation, the matmul's operand."""
    from kungfu_tpu.ops import moe

    sizes = jnp.asarray([8, 8], jnp.int32)
    up = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    w_down = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 4))
    _, vjp = jax.vjp(lambda up, w: moe._relu2_down(up, w, sizes), up, w_down)
    kept = [leaf for leaf in jax.tree.leaves(vjp) if hasattr(leaf, "shape")]
    assert sorted(leaf.shape for leaf in kept if leaf.ndim >= 2) == [
        (2, 8, 4), (16, 8)]


@pytest.mark.parametrize("biased", [False, True])
def test_under_a_selection_bias_a_live_chunk_costs_its_buffer(biased):
    """A share of less than an eighth keeps its chunks and its loop. Under a
    selection bias (the layer's balance is a step's that this program does
    not run) the groups handed to the experts fill every chunk the rows
    reach, the rows of no group in the last one, zeros that weigh nothing;
    without one the groups are the rows that came. Values and gradients are
    the same either way."""
    moe, x, router, experts, gates = _share_setup(E=64)
    chunk = moe._share_chunk(48, 3, 4, 64, biased)
    assert chunk == 40  # four balanced loads, whatever the bias
    mine = tuple(w[4:8] for w in experts)
    seen = []

    def spy(rows, experts, sizes):
        seen.append((rows, sizes))
        return moe.swiglu_experts(rows, experts, sizes)

    order = jnp.arange(48 * 3, dtype=jnp.int32)
    for sizes in ([5, 0, 7, 2], [0, 0, 0, 0], [chunk, 3, 0, chunk - 7]):
        sizes = jnp.array(sizes, jnp.int32)
        for i in range(-(-int(sizes.sum()) // chunk) or 1):
            moe._chunk_part(spy, 3, chunk, x, jnp.ones((48, 3)), mine, order,
                            sizes, i, biased)
            rows, groups = seen.pop()
            live = int(np.clip(int(sizes.sum()) - i * chunk, 0, chunk))
            assert float(jnp.abs(rows[live:]).max(initial=0.0)) == 0.0
            assert int(groups.sum()) == (chunk if biased else live)
            # each group's part of this chunk, but for the last group's filling
            assert groups[:3].tolist() == jnp.diff(
                jnp.clip(jnp.cumsum(sizes), i * chunk, (i + 1) * chunk),
                prepend=i * chunk)[:3].tolist()
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (64,))

    def part(x, router, mine, bias):
        return moe.moe_ffn(x, router, mine, top_k=3, gates=gates,
                           expert_fn=moe.swiglu_experts, held=(4, 4),
                           scores="sigmoid", bias=bias)[0]

    def by_hand(x, router, mine, bias):
        scores = jax.nn.sigmoid(x @ router)
        idx = jnp.argsort(-(scores + bias), axis=-1)[:, :3]
        top = jnp.take_along_axis(scores, idx, -1)
        weights = 2.5 * top / top.sum(-1, keepdims=True)
        out = jnp.zeros_like(x)
        for e in range(4):
            y = (jax.nn.silu(x @ mine[0][e]) * (x @ mine[1][e])) @ mine[2][e]
            out = out + jnp.sum(jnp.where(idx == 4 + e, weights, 0.0), -1)[:, None] * y
        return out

    assert "while" in str(jax.make_jaxpr(part)(x, router, mine, bias))
    np.testing.assert_allclose(np.asarray(part(x, router, mine, bias)),
                               np.asarray(by_hand(x, router, mine, bias)),
                               rtol=1e-4, atol=1e-5)
    grads = jax.grad(lambda *a: jnp.sum(part(*a) ** 2), (0, 2))(x, router, mine, bias)
    wants = jax.grad(lambda *a: jnp.sum(by_hand(*a) ** 2), (0, 2))(x, router, mine, bias)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(wants)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4, atol=1e-4)


def test_relu2_experts_feed_the_grouped_matmul_whole_widths(monkeypatch):
    """D and F go into the grouped matmul (`lax.ragged_dot` at these rows,
    `ops/grouped_matmul.py`'s rule) filled with zeros to multiples of
    `GROUPED_WIDTH` (3,072 and 2,048 for Nemotron-3-Nano's 2,688 and 1,856),
    and the result is the unfilled one's to the last bit."""
    from kungfu_tpu.ops import moe

    assert moe.GROUPED_WIDTH == 512
    assert [-(-w // 512) * 512 for w in (2688, 1856)] == [3072, 2048]
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    rows = jax.random.normal(ks[0], (12, 10))
    experts = (jax.random.normal(ks[1], (2, 10, 6)), jax.random.normal(ks[2], (2, 6, 10)))
    sizes = jnp.asarray([7, 4], jnp.int32)

    def shapes():
        jaxpr = jax.make_jaxpr(lambda r, e: moe.relu2_experts(r, e, sizes))(rows, experts)
        return [[v.aval.shape for v in eqn.invars[:2]] for eqn in _all_eqns(jaxpr.jaxpr)
                if eqn.primitive.name == "ragged_dot_general"]

    monkeypatch.setattr(moe, "GROUPED_WIDTH", 8)
    filled = moe.relu2_experts(rows, experts, sizes)
    assert shapes() == [[(12, 16), (2, 16, 8)], [(12, 8), (2, 8, 16)]]
    assert filled.shape == (12, 10)
    monkeypatch.setattr(moe, "GROUPED_WIDTH", 1)
    assert shapes() == [[(12, 10), (2, 10, 6)], [(12, 6), (2, 6, 10)]]
    np.testing.assert_array_equal(np.asarray(filled),
                                  np.asarray(moe.relu2_experts(rows, experts, sizes)))


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _all_eqns(sub)


# --- the count of the token-choices an expert (PR 50) --------------------------

# routings made to break a count, over 8 experts: (top_k, the experts that
# every token prefers by far; the rest of its choices fall where noise says)
COUNT_ROUTINGS = {
    "every_choice_on_one_expert": (1, [5]),
    "experts_that_get_none": (2, [1, 2, 6]),
    "top_k_is_every_expert": (8, []),
}
# the whole layer, a share's window at the first, a middle and the last
# experts, and the expert axis with room for every choice and with room
# for one a bucket
COUNT_PATHS = {"whole": None, "held_first": (0, 2), "held_middle": (3, 2),
               "held_last": (6, 2), "axis": 8.0, "axis_dropping": 1.0}


def _routed(preferred, T=32, D=8, F=4, E=8):
    """Positive tokens and a router whose `preferred` columns outweigh all
    others for every token."""
    ks = jax.random.split(jax.random.PRNGKey(len(preferred)), 4)
    x = 1.0 + jnp.abs(jax.random.normal(ks[0], (T, D)))
    router = 0.05 * jax.random.normal(ks[1], (D, E))
    router = router.at[:, jnp.asarray(preferred, jnp.int32)].add(1.0)
    w_in = jax.random.normal(ks[2], (E, D, F)) * 0.3
    w_out = jax.random.normal(ks[3], (E, F, D)) * 0.3
    return x, router, w_in, w_out


@pytest.mark.parametrize("path", COUNT_PATHS)
@pytest.mark.parametrize("routing", COUNT_ROUTINGS)
def test_counts_are_the_bincount_of_the_chosen_experts(routing, path):
    """`MoeAux.counts` is numpy's `bincount` of `MoeAux.chosen`: of all E
    experts in the whole layer, of the window held in a share (its `sizes`,
    the grouped matmuls' groups), and across an expert axis of the choices
    kept, a shard's own (at most the capacity of each (expert, choice)
    bucket)."""
    from kungfu_tpu.ops.moe import moe_ffn

    top_k, preferred = COUNT_ROUTINGS[routing]
    T, E = 32, 8
    x, router, w_in, w_out = _routed(preferred, T=T, E=E)
    how = COUNT_PATHS[path]
    if not path.startswith("axis"):
        first, count = how or (0, E)
        _, aux = jax.jit(lambda x, r, w: moe_ffn(
            x, r, w, top_k=top_k, held=how))(
                x, router, (w_in[first:first + count], w_out[first:first + count]))
        chosen = np.asarray(aux.chosen)
        want = np.bincount(chosen.ravel(), minlength=E)
        assert set(preferred[:top_k]) <= set(chosen[0].tolist())
        assert aux.counts.dtype == jnp.int32
        assert aux.counts.tolist() == want[first:first + count].tolist()
        return
    ep = 4
    mesh = _ep_mesh(ep)

    def shard_fn(x_sh, router, w_in_sh, w_out_sh):
        _, aux = moe_ffn(x_sh, router, (w_in_sh, w_out_sh), "ep", ep,
                         top_k=top_k, capacity_factor=how)
        return aux.counts[None], aux.chosen, aux.load_balance

    counts, chosen, balance = jax.jit(shard_map(
        shard_fn, mesh=mesh, in_specs=(P("ep"), P(), P("ep"), P("ep")),
        out_specs=(P("ep"), P("ep"), P()), check_vma=False))(
            x, router, w_in, w_out)
    chosen = np.asarray(chosen).reshape(ep, T // ep, top_k)
    capacity = max(1, int(how * (T // ep) / E))
    want = [sum(np.minimum(np.bincount(mine[:, j], minlength=E), capacity)
                for j in range(top_k)) for mine in chosen]
    assert np.asarray(counts).tolist() == [w.tolist() for w in want]
    dropped = T * top_k - int(np.asarray(counts).sum())
    assert (dropped > 0) == (path == "axis_dropping")
    # the balance loss sees every choice, kept or not: the mean of the shards'
    probs = jax.nn.softmax(jnp.dot(x, router, precision="highest"), -1)
    asked = np.stack([np.bincount(mine.ravel(), minlength=E) for mine in chosen])
    mean_probs = np.asarray(probs).reshape(ep, T // ep, E).mean(1)
    assert float(balance) == pytest.approx(float(np.mean(
        E * np.sum(asked / (T // ep * top_k) * mean_probs, -1))), rel=1e-5)


@pytest.mark.parametrize("chosen,E", [
    ([[3, 0], [3, 3], [1, 0]], 4),      # two dimensions, an expert with none
    ([7, 7, 7, 8, -1, 2], 8),           # 8 and -1 name no expert
    ([], 3),
])
def test_count_choices_is_numpys_bincount(chosen, E):
    from kungfu_tpu.ops.moe import _count_choices

    chosen = np.asarray(chosen, np.int32)
    inside = chosen[(chosen >= 0) & (chosen < E)]
    got = _count_choices(jnp.asarray(chosen), E)
    assert got.dtype == jnp.int32 and got.shape == (E,)
    assert got.tolist() == np.bincount(inside, minlength=E).tolist()


@pytest.mark.parametrize("held,biased", [(None, False), ((4, 4), False),
                                         ((4, 4), True)],
                         ids=["whole", "held", "held_under_a_bias"])
def test_the_forward_pass_counts_without_a_scatter(held, biased):
    """No `scatter-add` of one scalar a token-choice is left in the
    layer's forward pass (the v5e applies such updates one after another:
    `ops/moe._count_choices`). What stays: the whole layer's inverse order
    (a `scatter`, no sum), in a share its rows' way back, (chunk, D) into
    (T, D), and under a selection bias `_chunk_part`'s one-element
    `here.at[-1].add`, the last group's filling."""
    moe, x, router, experts, gates = _share_setup(E=16)
    first, count = held or (0, 16)
    mine = tuple(w[first:first + count] for w in experts)
    bias = jnp.linspace(-0.1, 0.1, 16) if biased else None
    jaxpr = jax.make_jaxpr(lambda x, r, w: moe.moe_ffn(
        x, r, w, top_k=3, gates=gates, expert_fn=moe.swiglu_experts,
        held=held, bias=bias))(x, router, mine)
    adds = [[v.aval.shape for v in eqn.invars] for eqn in _all_eqns(jaxpr.jaxpr)
            if eqn.primitive.name == "scatter-add"]
    scalars = [shapes for shapes in adds if len(shapes[2]) < 2]
    assert all(int(np.prod(updates)) == 1 for _, _, updates in scalars), scalars
    if held is None:
        assert adds == []
    else:  # the walk reaches the share's loop
        assert [operand for operand, _, _ in scalars] == [(count,)] * biased
        assert [operand for operand, _, _ in adds if len(operand) == 2] == [x.shape]


def test_reglu_experts_are_three_matrices_over_the_groups():
    """`reglu_experts`: rows in groups, one group an expert, y = w_down
    (relu(w_gate x) * w_up x), values and every gradient against a loop over
    the experts; rows past the groups' sum give nothing; the gated product is
    made again in the backward pass and not kept."""
    from kungfu_tpu.ops import moe

    E, D, F = 4, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    sizes = jnp.asarray([5, 0, 7, 3], jnp.int32)
    rows = jax.random.normal(ks[0], (20, D))  # 15 in groups, 5 in none
    w_gate, w_up = (0.5 * jax.random.normal(k, (E, D, F)) for k in ks[1:3])
    w_down = 0.5 * jax.random.normal(ks[3], (E, F, D))

    def by_hand(rows, w_gate, w_up, w_down):
        out, at = [], 0
        for e, n in enumerate(sizes.tolist()):
            x = rows[at:at + n]
            out.append((jnp.maximum(x @ w_gate[e], 0.0) * (x @ w_up[e])) @ w_down[e])
            at += n
        return jnp.concatenate(out + [jnp.zeros((20 - at, D))])

    weights = (w_gate, w_up, w_down)
    weight = jax.random.normal(jax.random.PRNGKey(1), (20, D))

    def both(f):
        """f's values and, of its sum weighed by `weight`, every gradient."""
        return jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *a: jnp.sum(f(*a) * weight), (0, 1, 2, 3))(*a)))(rows, *weights)

    got, grads = both(lambda *a: moe.reglu_experts(a[0], a[1:], sizes))
    want, wants = both(by_hand)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert not np.asarray(got[15:]).any()
    for g, w in zip(grads, wants):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5)
    assert not np.asarray(grads[1][1]).any()  # the expert with no row
    # silu in the relu's place is another function
    assert not np.allclose(np.asarray(moe.swiglu_experts(rows, weights, sizes)),
                           np.asarray(got), atol=1e-2)
    kept = jax.make_jaxpr(jax.grad(lambda r: jnp.sum(moe.reglu_experts(
        r, weights, sizes))))(rows)
    assert "checkpoint" in str(kept) or "remat" in str(kept)


def _routed_elsewhere(layout, top_k=2):
    """(with a routing handed in, making the same one itself) of `moe_ffn` in
    one of its three layouts, on the same rows: outputs, counts, chosen."""
    from kungfu_tpu.ops.moe import (Routing, dispatch_plan, moe_ffn,
                                    reglu_experts, renormalised_gates, route)

    T, D, F, E = 32, 8, 16, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (T, D), jnp.float32)
    router_w = jax.random.normal(jax.random.PRNGKey(1), (D, E), jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    w_gate, w_up = (0.3 * jax.random.normal(k, (E, D, F)) for k in ks[:2])
    w_down = 0.3 * jax.random.normal(ks[2], (E, F, D))
    how = dict(top_k=top_k, gates=renormalised_gates, expert_fn=reglu_experts)

    def layer(x, router_w, experts, given, planned=True, **where):
        routing = None
        if given:
            made = route(x, router_w, top_k)
            plan = (dispatch_plan(made[3], E, where.get("held"))
                    if planned and "axis_name" not in where else None)
            routing = Routing(made, plan)
        out, aux = moe_ffn(x, router_w, experts, **how, **where, routing=routing)
        return out, aux.counts, aux.chosen

    if layout == "axis":
        ep = 4
        mesh = _ep_mesh(ep)

        def over_the_axis(given):
            fn = shard_map(
                lambda x, r, *w: layer(x, r, tuple(a[0] for a in w), given,
                                       axis_name="ep", axis_size=ep,
                                       capacity_factor=float(E)),
                mesh=mesh, in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep")),
                out_specs=(P("ep"), P("ep"), P("ep")), check_vma=False)
            return jax.jit(fn)(x, router_w, *(w.reshape(ep, E // ep, *w.shape[1:])
                                              for w in (w_gate, w_up, w_down)))

        return over_the_axis(True), over_the_axis(False)
    held = (2, 4) if layout == "share" else None
    experts = tuple(w[2:6] if held else w for w in (w_gate, w_up, w_down))
    where = {"held": held} if held else {}
    runs = [jax.jit(lambda x, r, given=given, planned=planned: layer(
        x, r, experts, given, planned, **where))(x, router_w)
        for given, planned in ((True, True), (True, False), (False, True))]
    for a, b in zip(runs[0], runs[1]):  # with its plan, and the plan left to moe_ffn
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    return runs[0], runs[2]


@pytest.mark.parametrize("layout", ["one_shard", "share", "axis"])
def test_moe_ffn_given_a_routing_is_moe_ffn_making_the_same_one(layout):
    """One signature in the three layouts: a `Routing` that `route` made of
    the same rows, with `dispatch_plan`'s order or without it, gives the
    output, counts and choices that `moe_ffn` gives making its own."""
    given, made = _routed_elsewhere(layout)
    for a, b in zip(given, made):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_routing_from_other_rows_moves_the_gates_derivative_there():
    """Routed from rows h that are not the rows x the experts transform: the
    gates' derivative reaches h, and x's is what it is under a routing that
    is a constant; a routing of another shape is refused."""
    from kungfu_tpu.ops.moe import (Routing, dispatch_plan, moe_ffn,
                                    reglu_experts, renormalised_gates, route)

    T, D, F, E, top_k = 24, 8, 16, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x, h = (jax.random.normal(k, (T, D)) for k in ks[:2])
    router_w = jax.random.normal(ks[2], (D, E))
    experts = tuple(0.3 * jax.random.normal(k, shape) for k, shape in zip(
        ks[3:], ((E, D, F), (E, D, F), (E, F, D))))
    held = (2, 4)
    mine = tuple(w[2:6] for w in experts)

    def out(x, h, constant=False):
        made = route(h, router_w, top_k)
        routing = Routing(made, dispatch_plan(made[3], E, held))
        if constant:
            routing = jax.lax.stop_gradient(routing)
        y, aux = moe_ffn(x, router_w, mine, top_k=top_k, gates=renormalised_gates,
                         expert_fn=reglu_experts, held=held, routing=routing)
        return jnp.sum(jnp.square(y)), aux.chosen

    (dx, dh), chosen = jax.jit(jax.grad(out, (0, 1), has_aux=True))(x, h)
    (dx_fixed, dh_fixed), _ = jax.jit(jax.grad(
        lambda x, h: out(x, h, True), (0, 1), has_aux=True))(x, h)
    assert np.asarray(dh).any() and not np.asarray(dh_fixed).any()
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_fixed), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(chosen),
                                  np.asarray(route(h, router_w, top_k)[3]))
    assert (np.asarray(chosen) != np.asarray(route(x, router_w, top_k)[3])).any()
    with pytest.raises(ValueError, match="a routing of"):
        moe_ffn(x[:8], router_w, mine, top_k=top_k, held=held,
                routing=Routing(route(h, router_w, top_k)))
