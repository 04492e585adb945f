"""The serving step writes its new K/V into the pools in place
(``generation.step_ragged``: the stacked pools joined into one run of pages,
threaded through the layers, each page the step's rows land in written
whole once, in ``generation.page_windows`` windows).

Oracles that do not pass through ``generation.py``: the model's own full
forward (``models/llama.py``, ``models/gpt.py``) gives every position's
logits and, through hooks on its projections, every layer's K and V; the
pools are then written row by row here, into per-layer arrays.
"""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import generation as G
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     apply_rope)
from paddle_tpu.serving import EngineConfig, ServingEngine
from paddle_tpu.serving import engine as engine_mod

pytestmark = pytest.mark.serve

VOCAB, HIDDEN, HEADS = 61, 32, 4
DECODERS = ["llama", "gpt"]


def _model(kind, layers=3):
    paddle.seed(11)
    if kind == "llama":
        cfg = LlamaConfig.tiny(vocab_size=VOCAB, hidden_size=HIDDEN,
                               layers=layers, heads=HEADS, kv_heads=2, seq=64)
        cfg.use_flash_attention = False
        return LlamaForCausalLM(cfg)
    return GPTForCausalLM(GPTConfig.tiny(vocab_size=VOCAB, hidden_size=HIDDEN,
                                         layers=layers, heads=HEADS, seq=64))


def _truth(model, ids):
    """The model's own forward over one sequence: logits [S, V] and each
    layer's keys and values [L, S, kvh, hd] as the attention saw them."""
    kept = {}

    def keep(name):
        return lambda _layer, _inp, out: kept.__setitem__(name, out._data)

    s, hd = len(ids), HIDDEN // HEADS
    if isinstance(model, LlamaForCausalLM):
        blocks = [b.self_attn for b in model.model.layers]
        hooks = [p.register_forward_post_hook(keep((i, n)))
                 for i, b in enumerate(blocks)
                 for n, p in (("k", b.k_proj), ("v", b.v_proj))]
    else:
        blocks = [b.attn for b in model.transformer.h]
        hooks = [b.qkv_proj.register_forward_post_hook(keep((i, "qkv")))
                 for i, b in enumerate(blocks)]
    try:
        logits = model(paddle.to_tensor(np.asarray([ids], np.int32)))
    finally:
        for h in hooks:
            h.remove()
    ks, vs = [], []
    for i in range(len(blocks)):
        if (i, "qkv") in kept:
            qkv = kept[(i, "qkv")].reshape(1, s, 3, HEADS, hd)
            k, v = qkv[:, :, 1], qkv[:, :, 2]
        else:
            k = kept[(i, "k")].reshape(1, s, -1, hd)
            v = kept[(i, "v")].reshape(1, s, -1, hd)
            _, k = apply_rope(k, k, model.model.rope_cos._data[:s],
                              model.model.rope_sin._data[:s])
        ks.append(np.asarray(k[0]))
        vs.append(np.asarray(v[0]))
    return np.asarray(logits._data[0]), np.stack(ks), np.stack(vs)


# -- (a) one mixed step against a row-by-row write ------------------------------
BS, PAGES, TABLE, SLOTS, BUDGET = 8, 12, 4, 4, 24
#: page-table rows: sequence A on three scattered pages, B on one, C on two,
#: slot 3 holds no page at all
TABLES = np.array([[5, 2, 9, -1], [7, -1, -1, -1], [0, 11, -1, -1],
                   [-1, -1, -1, -1]], np.int32)
#: at the bound: A, B and C on two pages each, D on four
TABLES_AT_BOUND = np.array([[3, 8, -1, -1], [10, 1, -1, -1], [6, 0, -1, -1],
                            [11, 4, 9, 2]], np.int32)


def _rows(*groups):
    """Packed step arguments from (slot, positions, tokens, valid) groups,
    padded to the budget with invalid rows that point at slot 0, position 0
    (where sequence A's first row lives: written, they would clobber it)."""
    slot, pos, tok, valid = [], [], [], []
    for s_, positions, tokens, ok in groups:
        slot += [s_] * len(positions)
        pos += list(positions)
        tok += list(tokens)
        valid += [ok] * len(positions)
    pad = BUDGET - len(slot)
    return (np.asarray(tok + [1] * pad, np.int32),
            np.asarray(slot + [0] * pad, np.int32),
            np.asarray(pos + [0] * pad, np.int32),
            np.asarray(valid + [False] * pad, bool))


def _mixed(a, b, c):
    return [
        # every sequence's first chunk
        _rows((0, range(5), a[:5], True), (1, range(6), b[:6], True),
              (2, range(9), c[:9], True)),
        # A's next chunk crosses two page boundaries (positions 5..17 over
        # pages 5, 2 and 9); B and C decode; then rows that must write
        # nothing: an invalid one aimed at C's new slot, one whose slot has
        # no page, one past the table's last column
        _rows((0, range(5, 18), a[5:18], True), (1, [6], b[6:7], True),
              (2, [9], c[9:10], True), (2, [9], [3], False),
              (3, [3], [5], True), (1, [40], [7], True)),
    ]


def _at_the_bound(a, b, c, d):
    return [
        _rows((0, range(7), a[:7], True), (1, range(7), b[:7], True),
              (2, range(7), c[:7], True)),
        _rows((3, range(7), d[:7], True)),
        # every sequence's chunk starts in a page's last slot: A, B and C
        # two rows over two pages, D the other 18 rows over four, so the
        # step writes page_windows(24 rows, 4 sequences, 8 slots) = 10 pages
        _rows((0, [7, 8], a[7:9], True), (1, [7, 8], b[7:9], True),
              (2, [7, 8], c[7:9], True), (3, range(7, 25), d[7:25], True)),
    ]


CASES = {"mixed": (TABLES, (21, 7, 10), _mixed, 35),
         "at_the_bound": (TABLES_AT_BOUND, (9, 9, 9, 25), _at_the_bound, 52)}


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind", DECODERS)
def test_a_mixed_step_writes_what_a_row_by_row_write_does(kind, case):
    tables, lengths, plan, rows = CASES[case]
    model = _model(kind)
    dec = G._decoder_for(model)
    w = dec.weights(model)
    rng = np.random.default_rng(3)
    seqs = [rng.integers(1, VOCAB, n).tolist() for n in lengths]
    truth = [_truth(model, ids) for ids in seqs]
    shape = (dec.cache_entries, PAGES, dec.n_kv, BS, dec.hd)
    assert shape[0] == 3
    # what the pools hold before: noise, so a slot nobody wrote is told apart
    want_k = rng.standard_normal(shape).astype(np.float32)
    want_v = rng.standard_normal(shape).astype(np.float32)
    first_k = want_k.copy()
    kp, vp = jnp.asarray(want_k), jnp.asarray(want_v)
    step = jax.jit(lambda *args: engine_mod._ragged_forward(dec, None, w,
                                                            *args))
    checked = 0
    for tokens, slot, pos, valid in plan(*seqs):
        logits, exits, kp, vp = step(tokens, slot, pos, valid, tables, kp, vp)
        assert exits is None and kp.shape == vp.shape == shape
        pages = set()
        for r in range(BUDGET):
            col = pos[r] // BS
            if not valid[r] or col >= TABLE or tables[slot[r], col] < 0:
                continue                           # a row that writes nothing
            ref_logits, ks, vs = truth[slot[r]]
            pages.add(tables[slot[r], col])
            for layer in range(shape[0]):          # the plain write
                want_k[layer, tables[slot[r], col], :, pos[r] % BS] = \
                    ks[layer, pos[r]]
                want_v[layer, tables[slot[r], col], :, pos[r] % BS] = \
                    vs[layer, pos[r]]
            np.testing.assert_allclose(np.asarray(logits[r]),
                                       ref_logits[pos[r]], atol=3e-5)
            checked += 1
        assert len(pages) <= G.page_windows(BUDGET, SLOTS, BS) == 10
        np.testing.assert_allclose(np.asarray(kp), want_k, atol=1e-5)
        np.testing.assert_allclose(np.asarray(vp), want_v, atol=1e-5)
    assert checked == rows and (case == "mixed" or len(pages) == 10)
    # each row was written into its slot and no other slot moved by a bit
    moved = (np.asarray(kp) != first_k).any(axis=(0, 2, 4))      # [P, bs]
    assert moved.sum() == rows


def _most_pages(rows, seqs, bs):
    """By brute force: the most distinct pages that at most ``seqs`` runs
    of consecutive positions, ``rows`` positions in all, each run starting
    anywhere in a page, land in."""
    def parts(left, most, k):         # run lengths, longest first
        yield ()
        if k:
            for r in range(min(left, most), 0, -1):
                for rest in parts(left - r, r, k - 1):
                    yield (r,) + rest

    return max(sum(max(len({p // bs for p in range(o, o + r)})
                       for o in range(bs)) for r in runs)
               for runs in parts(rows, rows, seqs))


def test_the_page_windows_are_the_most_pages_a_step_can_write():
    for rows in range(1, 13):
        for seqs in range(1, 7):
            for bs in range(1, 6):
                assert G.page_windows(rows, seqs, bs) == \
                    _most_pages(rows, seqs, bs), (rows, seqs, bs)
    # the serving cells': decode and looped, chat, the two batch cells
    assert [G.page_windows(t, s, 16) for t, s in
            ((64, 16), (128, 32), (320, 256), (320, 192))] == [34, 68, 320, 320]


def _random_plan(rng, slots, pages):
    """(pages [BUDGET], offs [BUDGET]) within an entry, ``pages`` a dropped
    row: up to ``slots`` sequences, each a run of consecutive positions on
    its own pages (past its last page a row drops), invalid rows between,
    the rows in any order."""
    free = list(rng.permutation(pages))
    at, off = [], []
    for _ in range(rng.integers(1, slots + 1)):
        table = [free.pop() for _ in range(rng.integers(1, 4))]
        start = int(rng.integers(0, len(table) * BS))
        n = int(rng.integers(1, BUDGET - len(at) + 1))
        for p in range(start, start + n):
            at.append(table[p // BS] if p // BS < len(table) else pages)
            off.append(p % BS)
        if len(at) == BUDGET or len(free) < 3:
            break
    at += [pages] * (BUDGET - len(at))
    off += list(rng.integers(0, BS, BUDGET - len(off)))
    order = rng.permutation(BUDGET)
    return (np.asarray(at, np.int32)[order], np.asarray(off, np.int32)[order])


@pytest.mark.parametrize("slots", [SLOTS, BUDGET // 2])
def test_the_page_write_is_a_row_by_row_write_digit_for_digit(slots):
    """The write alone (``_entry_seams`` + ``_kv_write_pages`` over three
    cache entries) against numpy writing the same rows one by one: where
    the bound is under the rows (10 windows for 24) and where it reaches
    them (the row plan)."""
    entries, pages, kvh, d = 3, 2 * PAGES, 2, 4
    windows = G.page_windows(BUDGET, slots, BS)
    assert windows == (10 if slots == SLOTS else BUDGET)
    shape = (entries, pages, kvh, BS, d)

    @jax.jit
    def write(kp, vp, at, off, k, v):
        seam = G._entry_seams(shape, (at, off, windows, "xla"),
                              lambda *a, **k: 0)
        kf, vf = G._join_entries(kp, vp)
        for e in range(entries):
            kf, vf = G._kv_write_pages(kf, vf, k[e], v[e], seam(e)[0])
        return G._split_entries(kf, vf, shape)

    rng = np.random.default_rng(7)
    # the last step of the case at the bound, as the engine packs it
    bound = (np.asarray([3, 8, 10, 1, 6, 0, 11] + [4] * 8 + [9] * 8 + [2],
                        np.int32),
             np.asarray([7, 0] * 3 + [7] + list(range(8)) * 2 + [0],
                        np.int32))
    plans = [bound] + [_random_plan(rng, slots, pages) for _ in range(12)]
    for at, off in plans:
        kp = rng.standard_normal(shape).astype(np.float32)
        vp = rng.standard_normal(shape).astype(np.float32)
        k = rng.standard_normal((entries, BUDGET, 1, kvh, d)).astype(np.float32)
        v = rng.standard_normal((entries, BUDGET, 1, kvh, d)).astype(np.float32)
        assert len(set(at[at < pages].tolist())) <= windows
        got_k, got_v = write(kp, vp, at, off, k, v)
        for e in range(entries):
            for r in np.flatnonzero(at < pages):
                kp[e, at[r], :, off[r]] = k[e, r, 0]
                vp[e, at[r], :, off[r]] = v[e, r, 0]
        np.testing.assert_array_equal(np.asarray(got_k), kp)
        np.testing.assert_array_equal(np.asarray(got_v), vp)



# -- (a') the page kernel writes what the XLA write does, bit for bit ----------
KBS, KPAGES, KROWS = 16, 6, 16


def _chunk(page_of, start, n):
    """(pages, offs) of ``n`` rows at positions ``start ..`` of a sequence
    whose table is ``page_of`` (position // KBS -> page)."""
    return ([page_of[p // KBS] for p in range(start, start + n)],
            [p % KBS for p in range(start, start + n)])


def _kernel_plan(name):
    """(pages [KROWS], offs [KROWS], page-table rows) of one step within an
    entry, page ``KPAGES`` a row that writes nothing."""
    rows = {
        # five sequences decode, one row each, the rest of the budget padding
        "decode": ([0, 3, 5, 1, 4], [0, 15, 7, 3, 9], 5),
        # a 7-row chunk from position 12 over pages 2 and 4 beside three
        # decodes; an invalid row aimed at the chunk's first slot
        "mixed": tuple(a + b for a, b in zip(
            _chunk({0: 2, 1: 4}, 12, 7), ([1, 3, 0, KPAGES], [5, 0, 15, 12]))
        ) + (4,),
        # a whole budget of prompt from a page's last slot: 1 + 15 rows
        "crossing": _chunk({0: 5, 1: 0}, 15, 16) + (2,),
        # two real rows: the XLA plan's other windows are dropped ones
        "dropped": ([3, 1], [2, 11], 4),
        # eight page-table rows for 16 rows: the windows reach the rows (the
        # row plan), four sequences of four rows on one page each
        "row_plan": tuple(sum(c, []) for c in zip(*(
            _chunk({0: p}, 4 * k, 4) for k, p in enumerate([2, 0, 5, 3])))
        ) + (8,),
        # rows in any order, two of them on one slot: the first one wins
        "collide": ([4, 1, 4, 1, 2, 4], [6, 6, 7, 6, 0, 6], 4),
    }[name]
    pages, offs, seqs = rows
    pad = KROWS - len(pages)
    return (np.asarray(pages + [KPAGES] * pad, np.int32),
            np.asarray(offs + [0] * pad, np.int32), seqs)


KERNEL_CASES = [(plan, 2, "float32") for plan in
                ("decode", "mixed", "crossing", "dropped", "row_plan",
                 "collide")] + [
    ("mixed", 2, "bfloat16"), ("row_plan", 2, "bfloat16"),
    *(("mixed", kvh, dt) for kvh in (1, 8, 16, 32)
      for dt in ("float32", "bfloat16"))]


@pytest.mark.parametrize("plan,kvh,dtype", KERNEL_CASES,
                         ids=[f"{p}-kvh{k}-{d}" for p, k, d in KERNEL_CASES])
def test_the_page_kernel_writes_what_the_xla_write_does_bit_for_bit(
        plan, kvh, dtype, monkeypatch):
    """One step into both cache entries of two joined pools, through
    ``_entry_seams`` and ``_kv_write_pages`` as a decoder calls them: the
    XLA write (the CPU's path) and the page kernel (the path ``ragged.
    write_path`` picks when the serving kernels run, here interpreted).
    ``kvh`` 1 is a latent pool: one pool, no V."""
    from paddle_tpu.kernels import kv_write_pallas as kw
    from paddle_tpu.kernels import ragged_pallas
    from paddle_tpu.serving import ragged
    import engine_record
    pages, offs, seqs = _kernel_plan(plan)
    windows = G.page_windows(KROWS, seqs, KBS)
    assert (windows == KROWS) == (plan == "row_plan")
    entries, d, latent = 2, 128, kvh == 1
    shape = (entries, KPAGES, kvh, KBS, d)
    rng = np.random.default_rng(kvh)
    pools = [jnp.asarray(rng.standard_normal((entries * KPAGES,) + shape[2:]),
                         dtype) for _ in range(1 if latent else 2)]
    news = [[jnp.asarray(rng.standard_normal((KROWS, 1, kvh, d)), dtype)
             for _ in pools] for _ in range(entries)]

    def write():
        path = ragged.write_path(None, shape, dtype, KROWS)
        seam = G._entry_seams(shape, (jnp.asarray(pages), jnp.asarray(offs),
                                      windows, path), lambda *a, **k: 0)
        kf, vf = pools[0], None if latent else pools[1]
        for e in range(entries):
            kf, vf = G._kv_write_pages(kf, vf, news[e][0],
                                       None if latent else news[e][1],
                                       seam(e)[0])
        return [kf] if latent else [kf, vf]

    assert ragged.write_path(None, shape, dtype, KROWS) == "xla"
    want = write()
    monkeypatch.setattr(ragged_pallas, "_INTERPRET", True)
    assert ragged.write_path(None, shape, dtype, KROWS) == "page_kernel"
    got = write()
    for g, w, p in zip(got, want, pools):
        engine_record.assert_same_bits(g, w)
        assert not np.array_equal(np.asarray(g), np.asarray(p))
    # the kernel moves each distinct page the rows land in once: what the
    # engine counts as ``pages_written``
    step = kw.plan(jnp.asarray(pages), jnp.asarray(offs), KPAGES)
    real = set(pages[pages < KPAGES].tolist())
    assert int(step.count[0]) == len(real)
    assert sorted(np.asarray(step.pages)[:len(real)].tolist()) == sorted(real)


# -- (b) nothing the size of a pool is copied, stacked or sliced ----------------
@pytest.mark.parametrize("kind", DECODERS)
def test_the_step_program_holds_no_pool_sized_copy(kind):
    """The lowered step program of a 4-layer decoder: one layer's pool is
    no value's type (nothing is cut out of the stacked pools), and the
    stacked and the joined pools are results of nothing but the two
    reshapes and the page scatters (nothing is stacked or concatenated)."""
    model = _model(kind, layers=4)
    dec = G._decoder_for(model)
    w = dec.weights(model)
    t, pages = 16, 12
    tail = f"{dec.n_kv}x{BS}x{dec.hd}xf32>"
    i32 = jnp.zeros((t,), jnp.int32)
    pool = jnp.zeros((4, pages, dec.n_kv, BS, dec.hd), jnp.float32)
    text = jax.jit(lambda *a: engine_mod._engine_step_impl(
        dec, None, engine_mod._argmax_rows, None, *a)).lower(
            w, i32, i32, i32, i32, i32, jnp.zeros((t,), bool),
            jnp.zeros((SLOTS, TABLE), jnp.int32), pool, pool).as_text()
    one_layer = re.compile(rf"tensor<(1x)?{pages}x{tail}")
    assert not one_layer.search(text), one_layer.search(text).group(0)
    whole = re.compile(rf"-> tensor<(4x{pages}|{4 * pages})x{tail}")
    # a scatter prints its result type where its update region closes
    made_by = [m.group(1) if (m := re.search(r"stablehlo\.(\w+)", line))
               else line.split()[0]
               for line in text.splitlines()
               if whole.search(line) and "func.func" not in line]
    # a reshape in and a reshape out a pool; K and V scattered once a layer
    assert sorted(made_by) == ["reshape"] * 4 + ["})"] * 8, made_by
    assert text.count('"stablehlo.scatter"') == 8
    assert "stablehlo.concatenate" not in "".join(
        line for line in text.splitlines() if tail in line)


def _row_plan_seams(pool_shape, scatter, attend):
    """``generation._entry_seams`` as it was before the page windows: a
    page a packed row, whatever the rows."""
    entry_pages, all_pages = pool_shape[1], pool_shape[0] * pool_shape[1]
    pages, offs = scatter[:2]
    with jax.named_scope("kv_write"):
        lands = (pages[:, None, None] == pages[None, :, None]) \
            & (offs[None, :, None] == jnp.arange(pool_shape[3])[None, None, :])
        plan = lands.any(1), jnp.argmax(lands, axis=1).astype(jnp.int32)

    def seam(e):
        first_page = e * entry_pages
        with jax.named_scope("kv_write"):
            at = jnp.where(pages >= entry_pages, all_pages,
                           pages + first_page)
        return (at, *plan), partial(attend, first_page=first_page)

    return seam


@pytest.mark.parametrize("kind", DECODERS)
def test_where_the_windows_reach_the_rows_the_step_program_is_the_row_plans(
        kind, monkeypatch):
    """8 page-table rows for 16 packed rows: ``page_windows`` is 16, and
    the lowered step program is the one the row plan lowers to, letter for
    letter (as in the batch cells, 320 rows of 192 or 256 sequences)."""
    model = _model(kind, layers=2)
    dec = G._decoder_for(model)
    w = dec.weights(model)
    t, slots = 16, 8
    assert G.page_windows(t, slots, BS) == t
    i32 = jnp.zeros((t,), jnp.int32)
    pool = jnp.zeros((2, 12, dec.n_kv, BS, dec.hd), jnp.float32)

    def lowered():
        return jax.jit(lambda *a: engine_mod._engine_step_impl(
            dec, None, engine_mod._argmax_rows, None, *a)).lower(
                w, i32, i32, i32, i32, i32, jnp.zeros((t,), bool),
                jnp.zeros((slots, TABLE), jnp.int32), pool, pool).as_text()

    now = lowered()
    monkeypatch.setattr(G, "_entry_seams", _row_plan_seams)
    assert now == lowered()


@pytest.mark.parametrize("opts", [{}, dict(enable_prefix_cache=True),
                                  dict(spec_method="ngram",
                                       num_draft_tokens=3)])
def test_an_engines_steps_land_in_no_more_pages_than_the_windows(opts):
    """A random serving run (chunked prompts beside decode rows, shared
    prefixes, drafts): each step's ``pages_written``, counted on the host,
    is the distinct pages its packed rows land in by the step's own
    arguments, and never more than ``page_windows``; the telemetry sums
    both."""
    eng = _engine(_model("llama", layers=2), block_size=4, num_blocks=64,
                  **opts)
    windows = G.page_windows(16, 4, 4)
    assert eng._page_windows == windows == 10
    landed, counted = [], []
    call, count = eng._step_call, eng._pages_written

    def step_call(w, tokens, prev, feed, slots, positions, valid, tables,
                  *pools):
        s, p, ok, table = map(np.asarray, (slots, positions, valid, tables))
        col = p // 4
        ok = ok & (col < table.shape[1])
        at = table[s[ok], col[ok]]
        landed.append(len(set(at[at >= 0].tolist())))
        return call(w, tokens, prev, feed, slots, positions, valid, tables,
                    *pools)

    eng._step_call = step_call
    eng._pages_written = lambda plan: counted.append(count(plan)) \
        or counted[-1]
    rng = np.random.default_rng(9)
    shared = rng.integers(1, VOCAB, 12).tolist()
    for n in rng.integers(1, 40, 12):
        head = shared if opts.get("enable_prefix_cache") else []
        eng.submit(head + rng.integers(1, VOCAB, n).tolist(),
                   max_new_tokens=int(rng.integers(1, 12)))
    eng.run_until_idle(max_steps=500)
    assert landed == counted and max(counted) <= windows
    assert max(counted) > 4                 # steps that wrote many pages
    assert eng.telemetry()["kv_write"] == {
        "path": "xla", "pages_written": sum(counted),
        "page_windows": windows * len(counted)}



@pytest.mark.parametrize("kind", DECODERS)
def test_an_engine_on_the_page_kernel_serves_and_keeps_what_xla_does(
        kind, monkeypatch):
    """Chunked prompts beside decode rows, the serving kernels interpreted:
    the page kernel's engine serves the tokens and leaves the pools, bit
    for bit, that an engine writing with XLA does, and each says which
    write it ran."""
    import engine_record
    model = _model(kind, layers=2)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (13, 5, 21)]
    runs = engine_record.write_paths(monkeypatch, lambda: _engine(model),
                                     prompts, 6)
    (path, tokens, pools), (x_path, x_tokens, x_pools) = \
        runs["page_kernel"], runs["xla"]
    assert (path, x_path) == ("page_kernel", "xla")
    assert tokens == x_tokens
    for got, want in zip(pools, x_pools):
        engine_record.assert_same_bits(got, want)


# -- (c) pages that move whole still serve the same tokens ----------------------
def _engine(model, **kw):
    cfg = dict(max_seqs=4, token_budget=16, block_size=8, num_blocks=24,
               max_model_len=64, enable_prefix_cache=False)
    cfg.update(kw)
    return ServingEngine(model, EngineConfig(**cfg))


@pytest.mark.parametrize("kind", DECODERS)
def test_a_copied_and_a_handed_off_page_serve_the_same_tokens(kind):
    model = _model(kind)
    prompt = np.random.default_rng(5).integers(1, VOCAB, 13).tolist()
    (want,) = _engine(model).generate_batch([prompt], max_new_tokens=9)
    # hand-off: the prefill engine's pages, installed in a decode engine
    pre = _engine(model, role="prefill")
    pre.submit(prompt, max_new_tokens=9)
    pre.run_until_idle(max_steps=20)
    (req, record), = pre.pop_handoffs()
    eng = _engine(model, role="decode")
    eng.import_handoff(req, record)
    for _ in range(3):
        eng.step()
    assert not req.done and req.pos % 8
    # copy on write: another holder appears on the boundary page, the
    # sequence gets a private copy and the holder scribbles on its own
    old = req.pages[-1]
    eng.pool._ref[old] += 1
    kept, released, cow = eng.pool.truncate(req.pages, req.pos)
    assert released == 0 and cow == (old, kept[-1]) and cow[1] != old
    req.pages = kept
    eng._kp, eng._vp = engine_mod._copy_page(eng._kp, eng._vp, *cow)
    eng._kp = eng._kp.at[:, old].set(7.0)
    eng._vp = eng._vp.at[:, old].set(7.0)
    eng.pool.release([old])
    eng.run_until_idle(max_steps=50)
    assert req.done and req.error is None
    assert req.result(0) == want
