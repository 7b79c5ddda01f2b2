"""What the family files share: seeded ids and weights, the benchmark's plain
reference under ``jit``, and the driver of the ``[slots, W]`` slot program
(``forward_with_cache`` through a paged arena, the way the serving step feeds
it). A test pays for a program once: every draw of ``model.init`` is one
compile and not one a shape, the reference is one compile a length and not
one an operation, and the step is jitted once per (configuration, kernels on
or off, rows packed or by slot) for the process."""

import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.decoding import forward_with_cache, init_paged_cache
from deepspeed_tpu.ops.attention import attention_impl

F32 = jnp.float32


def ids_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n, dtype=np.int32)


def jit_init(model, key):
    """``model.init`` in float32 under one ``jit`` (eagerly every leaf's draw
    compiles for its shape). A fused draw may differ from the eager one in
    the last bit of a weight; the program and the reference read the same
    tree."""
    return jax.jit(functools.partial(model.init, dtype=F32))(key)


def init_params(model, seed=0, spread=0.1):
    """Seeded float32 weights whose norm scales are not one."""
    def make(key):
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            model.init(key, dtype=F32))
        out = []
        for i, (path, a) in enumerate(leaves):
            if getattr(path[-1], "key", "") == "scale":
                a = a * (1 + spread * jax.random.normal(jax.random.PRNGKey(i),
                                                        a.shape))
            out.append(a)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed))


@functools.cache
def reference_logits(fam):
    """``logits(params, ids, shape, last=None)``: ``fam.logits`` of the rows
    of ``ids`` (the last ``last`` of them) under ``jit``, on ``ids`` padded
    with zeros to a multiple of 128 rows. The reference is causal, a row
    does not depend on the rows after it, so the sequences of a file cost a
    compile a size and not one each (and not one an operation, as eagerly)."""
    static = [n for n in inspect.signature(fam.logits).parameters
              if n not in ("params", "ids")]
    padded_logits = jax.jit(fam.logits, static_argnames=static)

    def logits(params, ids, shape, last=None):
        n = len(ids)
        padded = np.zeros(-(-n // 128) * 128, np.int32)
        padded[:n] = ids
        return np.asarray(padded_logits(params, padded, shape))[
            n - (last or n):n]

    return logits


_STEPS = {}


def cached_step(cfg, kernels=False, token_budget=None, valid=False):
    """``forward_with_cache`` of the slot step, jitted once a program:
    ``step(params, tokens, caches, start, table, num_new, table_win=None)``.
    ``token_budget`` packs the rows; ``valid`` hands the routed layers
    ``pos < num_new`` as the engine does for rows laid out by slot."""
    key = (cfg, kernels, token_budget, valid)
    if key not in _STEPS:
        def step(params, tokens, caches, start, table, num_new,
                 table_win=None):
            real = (jnp.arange(tokens.shape[1])[None, :] < num_new[:, None]
                    if valid else None)
            with attention_impl("flash" if kernels else "xla"):
                return forward_with_cache(
                    cfg, params, tokens, caches, start, dtype=F32,
                    page_table=table, page_table_win=table_win,
                    num_new=num_new, token_budget=token_budget,
                    token_valid=real)

        _STEPS[key] = jax.jit(step)
    return _STEPS[key]


def drive(model, params, feeds, *, slots, width, pages_per_slot, page_size,
          kernels=False):
    """Run steps of the ``[slots, width]`` slot program with packed rows over
    an arena of ``pages_per_slot`` pages of ``page_size`` a slot: ``feeds``
    is a list of steps, each {slot: (ids of the rows fed, the slot's position
    before them)}; at most ``width`` rows a step in all (the scheduler's
    promise). Returns ({slot: [logits of every row fed, in order]}, the
    caches)."""
    cfg = model.config
    pages = slots * pages_per_slot
    caches = init_paged_cache(cfg, pages, page_size, F32, max_slots=slots)
    table = np.arange(pages, dtype=np.int32).reshape(slots, pages_per_slot)
    step = cached_step(cfg, kernels, token_budget=width)
    out = {s: [] for s in range(slots)}
    for feed in feeds:
        tokens = np.zeros((slots, width), np.int32)
        num_new = np.zeros(slots, np.int32)
        start = np.zeros(slots, np.int32)
        for slot, (part, at) in feed.items():
            tokens[slot, :len(part)] = part
            num_new[slot], start[slot] = len(part), at
        assert num_new.sum() <= width
        # an idle slot's row of the table is all NULL pages, as the
        # scheduler hands it: its padded writes land in the sink
        live = np.where((num_new > 0)[:, None], table, pages)
        logits, caches = step(
            params, jnp.asarray(tokens), caches, jnp.asarray(start),
            jnp.asarray(live), jnp.asarray(num_new))
        for slot, (part, _) in feed.items():
            out[slot].append(np.asarray(logits[slot, :len(part)]))
    return out, caches


def schedule(seqs, sizes):
    """Feeds that prefill ``seqs`` {slot: ids} side by side, slot ``s`` in
    chunks of ``sizes[s]`` rows (a list of sizes is cycled through)."""
    at = {s: 0 for s in seqs}
    turn = {s: 0 for s in seqs}
    feeds = []
    while any(at[s] < len(seqs[s]) for s in seqs):
        feed = {}
        for s, ids in seqs.items():
            if at[s] < len(ids):
                of = np.atleast_1d(sizes[s])
                n = int(of[turn[s] % len(of)])
                feed[s] = (ids[at[s]:at[s] + n], at[s])
                at[s], turn[s] = at[s] + n, turn[s] + 1
        feeds.append(feed)
    return feeds


def chunked_logits(model, params, ids, table, *, slot, chunk, page_size,
                   kernels=False, valid=False):
    """Logits [S, V] of every position of ``ids`` through the cached forward
    with rows laid out by slot, a chunk a call, in ``slot`` of the arena that
    ``table`` [slots, pages a slot] maps (the other slots idle)."""
    cfg = model.config
    slots = table.shape[0]
    caches = init_paged_cache(cfg, table.size, page_size, F32,
                              max_slots=slots)
    step = cached_step(cfg, kernels, valid=valid)
    out = []
    for lo in range(0, len(ids), chunk):
        part = ids[lo:lo + chunk]
        tokens = np.zeros((slots, chunk), np.int32)
        tokens[slot, :len(part)] = part
        num_new = np.zeros(slots, np.int32)
        num_new[slot] = len(part)
        start = np.zeros(slots, np.int32)
        start[slot] = lo
        logits, caches = step(
            params, jnp.asarray(tokens), caches, jnp.asarray(start),
            jnp.asarray(table, jnp.int32), jnp.asarray(num_new))
        out.append(np.asarray(logits[slot, :len(part)]))
    return np.concatenate(out)


def paged_forward(model, params, prompts, chunk, page_size, new_tokens=3,
                  budget=None):
    """Chunked prefill then greedy decode of ``prompts`` (one a slot)
    through the full and the window layers' paged pools, as the engine's
    step feeds them (``budget``: packed to that many rows); returns the
    logits of every real position, a row a slot, and the sequences."""
    cfg = model.config
    B = len(prompts)
    mp = -(-(max(map(len, prompts)) + new_tokens + chunk) // page_size)
    cache = init_paged_cache(cfg, B * mp, page_size, F32,
                             window_pages=B * mp)
    table = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    step = cached_step(cfg, token_budget=budget, valid=True)
    seqs = [list(p) for p in prompts]
    done = [0] * B
    rows = [[] for _ in range(B)]
    for _ in range(400):
        feed = np.zeros((B, chunk), np.int32)
        nn = np.zeros(B, np.int32)
        left = budget or B * chunk
        for b in range(B):
            n = min(chunk, len(seqs[b]) - done[b], left)
            feed[b, :n] = seqs[b][done[b]:done[b] + n]
            nn[b], left = n, left - n
        if not nn.any():
            break
        logits, cache = step(params, jnp.asarray(feed), cache,
                             jnp.asarray(done, jnp.int32), table,
                             jnp.asarray(nn), table)
        for b in range(B):
            rows[b].extend(np.asarray(logits[b, :nn[b]]))
            done[b] += int(nn[b])
            if nn[b] and done[b] == len(seqs[b]) and (
                    len(seqs[b]) < len(prompts[b]) + new_tokens):
                seqs[b].append(int(np.argmax(rows[b][-1])))
    return [np.stack(r) for r in rows], seqs
