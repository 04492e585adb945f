"""How the flash kernels size their tiles when nobody pinned or tuned them
(``flash_pallas.choose_tiles``): clockless, from shapes alone."""
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels import autotune
from paddle_tpu.kernels import flash_pallas as fp

HEADS = (64, 128, 256)
ITEMSIZES = (2, 4)


@pytest.mark.parametrize("kernel", fp.KERNELS)
@pytest.mark.parametrize("itemsize", ITEMSIZES)
@pytest.mark.parametrize("sq,sk", [(2048, 2048), (1024, 4096), (384, 384),
                                   (640, 1280), (8192, 8192)])
def test_tiles_are_multiples_of_128_that_divide_the_lengths(kernel, itemsize,
                                                            sq, sk):
    bq, bk, steps = fp.choose_tiles(kernel, sq, sk, 128, itemsize,
                                    batch_heads=3)
    assert bq % 128 == 0 and bk % 128 == 0
    assert bq <= fp.MAX_BLOCK and bk <= fp.MAX_BLOCK
    assert sq % bq == 0 and sk % bk == 0
    assert steps == 3 * (sq // bq) * (sk // bk)


@pytest.mark.parametrize("kernel", fp.KERNELS)
def test_short_sequences_are_one_block(kernel):
    assert fp.choose_tiles(kernel, 96, 96, 64, 4)[:2] == (96, 96)
    assert fp.choose_tiles(kernel, 128, 384, 64, 4)[:2] == (128, 384)
    assert fp.choose_tiles(kernel, 256, 256, 128, 2) == (256, 256, 1)


@pytest.mark.parametrize("kernel", fp.KERNELS)
@pytest.mark.parametrize("head_dim", HEADS)
@pytest.mark.parametrize("itemsize", ITEMSIZES)
@pytest.mark.parametrize("s", [2048, 8192])
def test_working_set_fits_the_budget(kernel, head_dim, itemsize, s):
    bq, bk, _ = fp.choose_tiles(kernel, s, s, head_dim, itemsize)
    assert fp.tile_vmem_bytes(kernel, bq, bk, head_dim, itemsize) \
        <= fp.VMEM_BUDGET_BYTES
    assert fp.VMEM_BUDGET_BYTES < fp.VMEM_LIMIT_BYTES


@pytest.mark.parametrize("kernel", fp.KERNELS)
def test_wider_operands_never_get_larger_tiles(kernel):
    """A float32 call has twice the tile bytes and a head of 256 halves what
    fits: the tiles' area never grows with either."""
    def area(head_dim, itemsize):
        bq, bk, _ = fp.choose_tiles(kernel, 8192, 8192, head_dim, itemsize)
        return bq * bk
    for head_dim in HEADS:
        assert area(head_dim, 4) <= area(head_dim, 2)
    for itemsize in ITEMSIZES:
        assert area(256, itemsize) <= area(128, itemsize) \
            <= area(64, itemsize)


def test_the_cells_call_is_a_few_hundred_steps():
    """cgpt13-train-2k's attention, [8 x 16, 2048, 128] bfloat16 causal:
    32,768 grid steps a call on 128 x 128 tiles, a few thousand at most
    now, in every kernel."""
    assert 128 * (2048 // 128) ** 2 == 32768
    for kernel in fp.KERNELS:
        steps = fp.choose_tiles(kernel, 2048, 2048, 128, 2,
                                batch_heads=128).grid_steps
        assert 128 <= steps <= 2048, (kernel, steps)


def test_unknown_kernel_is_refused():
    with pytest.raises(ValueError, match="not one of"):
        fp.tile_vmem_bytes("bwd", 128, 128, 128, 2)


@pytest.mark.parametrize("which,kernel", [("flash_fwd", "fwd"),
                                          ("flash_bwd", "dq"),
                                          ("flash_bwd", "dkv"),
                                          ("flashmask_fwd", "fwd"),
                                          ("flashmask_bwd", "dkv")])
def test_pinned_then_tuned_then_sized_from_the_shapes(which, kernel):
    q = jnp.zeros((2, 4, 2048, 128), jnp.bfloat16)
    sized = fp.choose_tiles(kernel, 2048, 2048, 128, 2)[:2]
    autotune.clear()
    try:
        assert fp._resolve_blocks(which, kernel, q, q, True, None,
                                  None) == sized
        autotune.record("flash_fwd", (2048, 2048, 128, "bfloat16", True),
                        (256, 128))
        assert fp._resolve_blocks(which, kernel, q, q, True, None,
                                  None) == (256, 128)
        # an explicit argument beats the recorded winner, one side at a time
        assert fp._resolve_blocks(which, kernel, q, q, True, 128,
                                  None) == (128, 128)
        assert fp._resolve_blocks(which, kernel, q, q, True, 1024,
                                  512) == (1024, 512)
        # another signature is still sized from its shapes
        k = jnp.zeros((2, 4, 4096, 128), jnp.bfloat16)
        assert fp._resolve_blocks(which, kernel, q, k, True, None, None) \
            == fp.choose_tiles(kernel, 2048, 4096, 128, 2)[:2]
    finally:
        autotune.clear()


@pytest.mark.parametrize("sq,sk,head_dim,itemsize", [
    (2048, 2048, 128, 2), (1024, 2048, 128, 2), (4096, 4096, 64, 4),
    (8192, 8192, 256, 4), (96, 96, 64, 4)])
def test_first_candidate_is_the_sized_default(sq, sk, head_dim, itemsize):
    cands = autotune.flash_block_candidates(sq, sk, head_dim, itemsize)
    assert cands[0] == fp.choose_tiles("fwd", sq, sk, head_dim, itemsize)[:2]
    assert len(set(cands)) == len(cands)
    for bq, bk in cands:
        assert sq % bq == 0 and sk % bk == 0
