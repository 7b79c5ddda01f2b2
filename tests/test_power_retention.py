"""The power-retention kernel (ops/pallas/power_retention.py, interpret mode,
small head size) against its plain twin and against the attention form, which
has no ``phi`` and no state."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import power_retention as pr

HD, KV, G = 16, 2, 5
EPS = 1e-6
# packed rows a program takes at head size 128: the whole head (the rule's own
# choice), PR 48's 13 and one
TILES = {"whole": 65, "13rows": 13, "1row": 1}


def draw(seed, B, T, gate_mean=-1.0, hd=HD):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (B, T, KV * G, hd), jnp.float32)
    k = jax.random.normal(keys[1], (B, T, KV, hd), jnp.float32)
    v = jax.random.normal(keys[2], (B, T, KV, hd), jnp.float32)
    g = jax.nn.log_sigmoid(
        gate_mean + 2.0 * jax.random.normal(keys[3], (B, T, KV), jnp.float32))
    return q, k, v, g


@jax.jit
def attention_form(q, k, v, g):
    """Row i over every row j <= i of the whole sequence: no state."""
    B, T, H, hd = q.shape
    with jax.default_matmul_precision("highest"):
        c = jnp.cumsum(g, axis=1)                                # [B, T, KV]
        qk = jnp.einsum("bickd,bjcd->bckij",
                        q.reshape(B, T, KV, G, hd), k) * hd ** -0.5
        seen = jnp.tril(jnp.ones((T, T), bool))
        diff = (c[:, :, None] - c[:, None, :]).transpose(0, 3, 1, 2)
        a = jnp.where(seen, jnp.exp(jnp.where(seen, diff, 0.0)), 0.0)[
            :, :, None] * qk * qk
        num = jnp.einsum("bckij,bjce->bicke", a, v)
        den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)
    return (num / (den[..., None] + EPS)).reshape(B, T, H, hd)


R = HD // 2 + 1  # packed rows of a key's symmetric square


def zeros(B, L=2, hd=HD):
    return (jnp.zeros((L, B, KV, hd // 2 + 1, hd, hd), jnp.float32),
            jnp.zeros((L, B, KV, hd // 2 + 1, 1, hd), jnp.float32))


def run_chunks(fn, q, k, v, g, sizes, S):
    """Feed the sequence in chunks of ``sizes`` rows a slot (a list a slot),
    each padded to ``S`` rows; returns the real rows' outputs in order."""
    B = q.shape[0]
    at = np.zeros(B, np.int64)
    leaves = None
    outs = [[] for _ in range(B)]
    # (cut and padded on the host: on the device every chunk size would
    # compile its own slice and concatenation)
    q, k, v, g = (np.asarray(a) for a in (q, k, v, g))
    for step in zip(*sizes):
        nn = np.asarray(step)
        pad = lambda a: jnp.asarray(np.stack([
            np.concatenate([a[b, at[b]:at[b] + nn[b]], np.full(
                (S - nn[b], *a.shape[2:]), 7.0, a.dtype)]) for b in range(B)]))
        o, leaves = fn(pad(q), pad(k), pad(v), pad(g), leaves,
                       jnp.asarray(at, jnp.int32), jnp.asarray(nn, jnp.int32))
        o = np.asarray(o)
        for b in range(B):
            outs[b].append(o[b, :nn[b]])
        at += nn
    return [np.concatenate(o) for o in outs], leaves


@functools.cache
def make_kernel(budget=pr.STATE_VMEM_BYTES):
    """The kernel under ``jit``, one for each ``STATE_VMEM_BYTES`` it is
    traced under (the tile is chosen while tracing)."""
    return jax.jit(lambda q, k, v, g, state, norm, cl, nn, layer:
                   pr.power_retention(q, k, v, g, state, norm, cl, nn,
                                      layer=layer, scale=q.shape[-1] ** -0.5,
                                      eps=EPS, interpret=True))


kernel = make_kernel()


def tiled_kernel(monkeypatch, rows, hd=128):
    """The kernel traced under a budget that holds ``rows`` packed rows a
    program at head size ``hd``."""
    monkeypatch.setattr(pr, "STATE_VMEM_BYTES", 4 * rows * hd * hd * 4)
    assert pr.tile_rows(hd) == rows
    return make_kernel(pr.STATE_VMEM_BYTES)


dense = jax.jit(lambda q, *a: pr.dense_power_retention(
    q, *a, scale=q.shape[-1] ** -0.5, eps=EPS))


def kernel_fn(layer=1, kernel=kernel):
    def fn(q, k, v, g, leaves, cl, nn):
        o, *leaves = kernel(q, k, v, g, *(
            leaves or zeros(q.shape[0], hd=q.shape[-1])), cl, nn, layer)
        return o, leaves
    return fn


def dense_fn(q, k, v, g, leaves, cl, nn):
    leaves = leaves or [a[0] for a in zeros(q.shape[0], 1, q.shape[-1])]
    o, *leaves = dense(q, k, v, g, *leaves, cl, nn)
    return o, leaves


def test_phi_is_the_square_of_the_dot_product_and_never_the_outer_product():
    a, b = jax.random.normal(jax.random.PRNGKey(0), (2, 7, HD))
    got = jnp.sum(pr.phi(a) * pr.phi(b, key=True), axis=(-2, -1))
    np.testing.assert_allclose(got, jnp.sum(a * b, axis=-1) ** 2, rtol=1e-5,
                               atol=1e-5)
    assert pr.expanded_dim(128) == 8320 < 128 * 128
    # 8,256 products and 64 lanes that hold nothing
    assert int((pr._tables(128)[2] > 0).sum()) == 128 * 129 // 2

def test_the_tile_divides_the_packed_rows_and_its_buffers_fit_the_limit(
        monkeypatch):
    # a kv head's 4.26 MB whole, two buffers each way, inside the limit the
    # call asks for, which is under its siblings' cap
    assert pr.tile_rows(128) == 65 and pr.tile_rows(16) == 9
    limit = pr.vmem_limit(65, 128, G, 128, 2)
    assert 4 * 65 * 128 * 128 * 4 <= pr.STATE_VMEM_BYTES < limit
    assert 2 * 4 * 65 * 128 * 128 * 4 <= limit <= pr.VMEM_CAP == 96 << 20
    # a head too large for the budget: the most packed rows that divide it
    assert pr.tile_rows(256) in (1, 3, 43) and 129 % pr.tile_rows(256) == 0
    assert 4 * pr.tile_rows(256) * 256 * 256 * 4 <= pr.STATE_VMEM_BYTES
    for rows in TILES.values():
        monkeypatch.setattr(pr, "STATE_VMEM_BYTES", 4 * rows * 128 * 128 * 4)
        assert pr.tile_rows(128) == rows and 65 % rows == 0
        assert pr.vmem_limit(rows, 128, G, 128, 2) <= pr.VMEM_CAP
    monkeypatch.setattr(pr, "STATE_VMEM_BYTES", 0)
    assert pr.tile_rows(128) == 1


# two slots; the second runs other chunk sizes and idles in places
CHUNKS = {
    "ones": ([1] * 6, [1, 0, 1, 1, 0, 1]),
    "twos": ([2, 2, 2], [2, 0, 2]),
    "ragged": ([5, 1, 7, 3], [8, 2, 0, 1]),
}


@pytest.mark.parametrize("tile", ["hd16", "hd48", *TILES])
@pytest.mark.parametrize("name", sorted(CHUNKS))
def test_kernel_matches_its_twin_and_the_attention_form(name, tile,
                                                        monkeypatch):
    # at head sizes 16 and 48 (no multiple of the walk's 32 value channels)
    # the tile is the rule's, the whole head; at 128 the whole head, 13 rows
    # and one row a program
    sizes = CHUNKS[name]
    T = max(sum(s) for s in sizes)
    hd = {"hd16": HD, "hd48": 48}.get(tile, 128)
    fn = kernel_fn(kernel=tiled_kernel(monkeypatch, TILES[tile])
                   ) if tile in TILES else kernel_fn()
    q, k, v, g = draw(3, 2, T, hd=hd)
    want = attention_form(q, k, v, g)
    got, (state, norm) = run_chunks(fn, q, k, v, g, sizes, S=8)
    twin, (tstate, tnorm) = run_chunks(dense_fn, q, k, v, g, sizes, S=8)
    # the twin reads a row's own share through phi too, and over 8,256
    # products a row whose normaliser is small loses a digit the kernel's
    # decode path keeps (it takes that share from q . k itself)
    twin_rtol = 2e-4 if hd == HD else 1e-3
    for b, size in enumerate(sizes):
        n = sum(size)
        np.testing.assert_allclose(got[b], want[b, :n], rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(twin[b], want[b, :n], rtol=twin_rtol,
                                   atol=2e-5)
    np.testing.assert_allclose(state[1], tstate, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(norm[1], tnorm, rtol=1e-4, atol=1e-5)
    # the other layer of the stack was not touched
    assert not np.any(np.asarray(state[0])) and not np.any(np.asarray(norm[0]))


def test_a_decode_row_leaves_the_same_bits_whatever_the_tile(monkeypatch):
    # ONE real row a slot (and an idle slot) from a state that holds
    # something: ``decay * s0 + v phi_k`` is one expression an element, so
    # neither leaf may depend on how many packed rows a program takes
    B, hd = 3, 128
    q, k, v, g = draw(11, B, 8, hd=hd)
    keys = jax.random.split(jax.random.PRNGKey(12), 2)
    state = jax.random.normal(keys[0], (1, B, KV, 65, hd, hd), jnp.float32)
    norm = jax.random.uniform(keys[1], (1, B, KV, 65, 1, hd), jnp.float32)
    cl = jnp.asarray([40, 0, 40], jnp.int32)
    nn = jnp.asarray([1, 1, 0], jnp.int32)
    got = {name: tiled_kernel(monkeypatch, rows)(
        q, k, v, g, state, norm, cl, nn, 0) for name, rows in TILES.items()}
    out, after, nafter = got["whole"]
    for name in ("13rows", "1row"):
        np.testing.assert_array_equal(got[name][1], after)
        np.testing.assert_array_equal(got[name][2], nafter)
        np.testing.assert_allclose(got[name][0][:, 0], out[:, 0], rtol=1e-5,
                                   atol=1e-6)
    # the rows moved, the slot at position 0 forgot what it held, the idle
    # slot kept every bit
    assert np.any(np.asarray(after[0, 0]) != np.asarray(state[0, 0]))
    _, fresh, nfresh = got["whole"]
    zero = tiled_kernel(monkeypatch, 65)(
        q, k, v, g, jnp.zeros_like(state), jnp.zeros_like(norm), cl, nn, 0)
    np.testing.assert_array_equal(fresh[0, 1], zero[1][0, 1])
    np.testing.assert_array_equal(nfresh[0, 1], zero[2][0, 1])
    np.testing.assert_array_equal(after[0, 2], state[0, 2])
    np.testing.assert_array_equal(nafter[0, 2], norm[0, 2])


def test_a_slot_with_no_real_row_gets_its_leaves_back_bit_for_bit():
    q, k, v, g = draw(5, 2, 8)
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    state = jax.random.normal(keys[0], (2, 2, KV, R, HD, HD), jnp.float32)
    norm = jax.random.uniform(keys[1], (2, 2, KV, R, 1, HD), jnp.float32)
    _, after, nafter = kernel(
        q, k, v, g, state, norm, jnp.asarray([40, 40], jnp.int32),
        jnp.asarray([0, 3], jnp.int32), 0)
    np.testing.assert_array_equal(after[0, 0], state[0, 0])
    np.testing.assert_array_equal(nafter[0, 0], norm[0, 0])
    np.testing.assert_array_equal(after[1], state[1])
    assert np.any(np.asarray(after[0, 1]) != np.asarray(state[0, 1]))


@pytest.mark.parametrize("rows", [1, 4])
def test_a_chunk_at_position_0_starts_from_zeros(rows):
    q, k, v, g = draw(6, 1, 8)
    dirty = (jnp.full((1, 1, KV, R, HD, HD), 3.0),
             jnp.full((1, 1, KV, R, 1, HD), 3.0))
    args = (q, k, v, g)
    cl, nn = jnp.zeros(1, jnp.int32), jnp.asarray([rows], jnp.int32)
    got = kernel(*args, *dirty, cl, nn, 0)
    want = kernel(*args, *zeros(1, 1), cl, nn, 0)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a[:, :rows] if a.ndim == 4 else a,
                                      b[:, :rows] if b.ndim == 4 else b)
    # and a chunk further on does read what the slot held
    on = kernel(*args, *dirty, cl + 5, nn, 0)
    assert np.any(np.asarray(on[0][:, :rows]) != np.asarray(want[0][:, :rows]))


@pytest.mark.parametrize("gate_mean", [12.0, -20.0])
def test_gates_near_0_and_near_minus_20(gate_mean):
    # log sigmoid(12) = -6e-6 (nothing forgotten); log sigmoid(-20) = -20 a
    # row (everything but the row itself, whose a_ii carries no gate)
    q, k, v, _ = draw(7, 2, 12)
    g = jax.nn.log_sigmoid(jnp.full((2, 12, KV), gate_mean, jnp.float32))
    want = attention_form(q, k, v, g)
    got, _ = run_chunks(kernel_fn(0), q, k, v, g, ([5, 1, 6], [1, 8, 3]), S=8)
    for b in range(2):
        np.testing.assert_allclose(got[b], want[b], rtol=3e-4, atol=3e-5)
    assert np.all(np.isfinite(np.asarray(got[0])))


def test_the_five_query_heads_of_a_group_read_one_state():
    # heads of one group fed the same query give the same output, and the
    # state is a kv head's: its shape has no query-head axis
    q, k, v, g = draw(8, 1, 8)
    same = jnp.broadcast_to(q[:, :, :1], q.shape)
    state, norm = zeros(1, 1)
    o, state, norm = kernel(same, k, v, g, state, norm,
                            jnp.zeros(1, jnp.int32),
                            jnp.asarray([8], jnp.int32), 0)
    for h in range(1, G):
        np.testing.assert_array_equal(o[:, :, h], o[:, :, 0])
    assert np.any(np.asarray(o[:, :, G]) != np.asarray(o[:, :, 0]))
    assert state.shape == (1, 1, KV, R, HD, HD)
    assert norm.shape == (1, 1, KV, R, 1, HD)
    assert R * HD == pr.expanded_dim(HD)


def test_the_kernel_says_why_it_declines():
    q = jnp.zeros((1, 16, 16, 128))
    assert pr.kernel_reasons(q, jnp.zeros((1, 16, 2, 128)), False)  # 8 a group
    assert pr.kernel_reasons(q[:, :, :14], jnp.zeros((1, 16, 2, 128)), False)
    assert not pr.kernel_reasons(q[:, :, :10], jnp.zeros((1, 16, 2, 128)),
                                 False)
    assert pr.kernel_reasons(q[..., :64], jnp.zeros((1, 16, 4, 64)), False)
    assert not pr.kernel_reasons(q[..., :64], jnp.zeros((1, 16, 4, 64)), True)
