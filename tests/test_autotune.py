"""Kernel autotune cache logic (reference: phi/kernels/autotune/
auto_tune_base.h + switch_autotune.h) — injected timer, no TPU needed."""
import os

import paddle_tpu as paddle
from paddle_tpu.kernels import autotune


def setup_function(_):
    autotune.clear()
    autotune.set_cache_path(None)


def test_off_by_default_picks_first():
    calls = []
    best = autotune.pick("k", (1, 2), [(128, 128), (256, 128)],
                         run=lambda c: calls.append(c))
    assert best == (128, 128)
    assert not calls  # no timing when the flag is off


def test_times_candidates_and_caches():
    paddle.set_flags({"FLAGS_use_autotune": True})
    try:
        times = {(128, 128): 0.5, (256, 128): 0.1, (256, 256): 0.9}
        runs = []

        def run(c):
            runs.append(c)
            return c

        def timer(fn):
            c = fn()
            return times[c]

        best = autotune.pick("k", ("sig",), list(times), run, timer=timer)
        assert best == (256, 128)
        runs.clear()
        again = autotune.pick("k", ("sig",), list(times), run, timer=timer)
        assert again == (256, 128)
        assert not runs  # cache hit: no re-timing
    finally:
        paddle.set_flags({"FLAGS_use_autotune": False})


def test_failing_candidate_skipped():
    paddle.set_flags({"FLAGS_use_autotune": True})
    try:
        def run(c):
            if c == (512, 512):
                raise ValueError("bad tiling")
            return c

        best = autotune.pick("k2", ("s",), [(512, 512), (128, 128)], run,
                             timer=lambda fn: (fn(), 1.0)[1])
        assert best == (128, 128)
    finally:
        paddle.set_flags({"FLAGS_use_autotune": False})


def test_disk_cache_roundtrip(tmp_path):
    paddle.set_flags({"FLAGS_use_autotune": True})
    try:
        p = str(tmp_path / "tune.json")
        autotune.set_cache_path(p)
        best = autotune.pick("k3", (7,), [(128, 128), (256, 256)],
                             run=lambda c: c,
                             timer=lambda fn: 0.1 if fn() == (256, 256)
                             else 0.9)
        assert best == (256, 256)
        assert os.path.exists(p)
        autotune.clear()  # wipe in-process cache; disk must serve the hit
        timed = []
        again = autotune.pick("k3", (7,), [(128, 128), (256, 256)],
                              run=lambda c: timed.append(c),
                              timer=lambda fn: 0.0)
        assert again == (256, 256) and not timed
    finally:
        paddle.set_flags({"FLAGS_use_autotune": False})
        autotune.set_cache_path(None)


def test_flash_candidates_divisible():
    from paddle_tpu.kernels.flash_pallas import choose_tiles
    cands = autotune.flash_block_candidates(1024, 2048, 128)
    # the untimed default is what the kernel sizes for itself, not 128x128
    assert cands[0] == choose_tiles("fwd", 1024, 2048, 128, 2)[:2]
    assert (128, 128) in cands
    for q, k in cands:
        assert 1024 % q == 0 and 2048 % k == 0
    assert autotune.flash_block_candidates(96, 96, 64) == [(96, 96)]


def test_tune_signature_matches_resolver():
    """The bshd wrapper, the Pallas resolver, and the bench probe must
    agree on the cache key, or probe-tuned blocks never reach training
    (round-5 review finding)."""
    import jax.numpy as jnp
    from paddle_tpu.kernels import autotune
    from paddle_tpu.kernels.flash_attention import _tune_signature
    from paddle_tpu.kernels.flash_pallas import _resolve_blocks
    q_bshd = jnp.zeros((2, 2048, 12, 128), jnp.bfloat16)
    sig = _tune_signature(q_bshd, q_bshd, True)
    assert sig == (2048, 2048, 128, "bfloat16", True)
    autotune.record("flash_fwd", sig, (256, 512))
    try:
        q_bhsd = jnp.zeros((2, 12, 2048, 128), jnp.bfloat16)
        assert _resolve_blocks("flash_fwd", "fwd", q_bhsd, q_bhsd, True,
                               None, None) == (256, 512)
        # flashmask inherits the dense-causal winner
        assert _resolve_blocks("flashmask_fwd", "fwd", q_bhsd, q_bhsd, True,
                               None, None) == (256, 512)
    finally:
        autotune.clear()


def test_cached_memoizes_misses(tmp_path):
    import json as _json
    from paddle_tpu.kernels import autotune
    p = tmp_path / "cache.json"
    p.write_text(_json.dumps({}))
    autotune.set_cache_path(str(p))
    try:
        autotune.clear()
        assert autotune.cached("flash_fwd", (1, 1, 1, "x", True)) is None
        # poison the file: a re-read would now crash json parsing… but a
        # memoized miss never re-reads
        p.write_text("{not json")
        assert autotune.cached("flash_fwd", (1, 1, 1, "x", True)) is None
        # record() overwrites the sentinel
        autotune.record("flash_fwd", (1, 1, 1, "x", True), (256, 256))
        assert autotune.cached("flash_fwd",
                               (1, 1, 1, "x", True)) == (256, 256)
    finally:
        autotune.set_cache_path(None)
        autotune.clear()


def test_flash_bwd_inherits_fwd_winner():
    """Runtime tune_blocks records only flash_fwd; the resolver's
    fallback chain must give the backward the same winner (round-5
    review finding)."""
    import jax.numpy as jnp
    from paddle_tpu.kernels import autotune
    from paddle_tpu.kernels.flash_pallas import _resolve_blocks
    sig = (4096, 4096, 64, "bfloat16", True)
    autotune.record("flash_fwd", sig, (512, 256))
    try:
        q = jnp.zeros((1, 2, 4096, 64), jnp.bfloat16)
        assert _resolve_blocks("flash_bwd", "dq", q, q, True, None,
                               None) == (512, 256)
        assert _resolve_blocks("flashmask_bwd", "dkv", q, q, True, None,
                               None) == (512, 256)
        # a bwd-specific entry (the hardware probe writes one) wins
        autotune.record("flash_bwd", sig, (128, 512))
        assert _resolve_blocks("flash_bwd", "dq", q, q, True, None,
                               None) == (128, 512)
    finally:
        autotune.clear()
