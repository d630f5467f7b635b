"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode runs a kernel body as plain jnp on the CPU, so the
equivalence tests in ``test_kernels.py``/``test_rerank.py`` say nothing
about whether the chip's compiler (Mosaic) accepts the kernel: block
shapes off the (8, 128) tiling, lane offsets it cannot prove aligned and
loop carries with an unsupported layout all pass there and fail here.
Each test lowers one kernel at the widths the SSH query path uses for a
v5e chip that is described, not attached, and checks that the compiled
program holds the kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library at a time, and a worker that described
it while collecting would change what the other workers collect.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import collision_count, count_sketch, dtw_wavefront, \
    sketch_conv


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs in /tmp
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _arg(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("b,m", [(1024, 128), (64, 2048)])
def test_sketch_conv_compiles(one_chip, b, m):
    """ECG encoder widths: W=80, δ=3, F=1."""
    _compile_kernel(lambda x, f: sketch_conv.sketch_conv(x, f, 3),
                    _arg(one_chip, (b, m)), _arg(one_chip, (80, 1)))


@pytest.mark.parametrize("m,band", [(128, 6), (2048, 102)])
def test_dtw_wavefront_pairs_compiles(one_chip, m, band):
    """The batched re-rank's survivor-pair DTW, early-abandoning."""
    p = 1024
    _compile_kernel(
        lambda q, x, t: dtw_wavefront.dtw_wavefront_pairs(q, x, band,
                                                          threshold=t),
        _arg(one_chip, (p, m)), _arg(one_chip, (p, m)),
        _arg(one_chip, (p,)))


@pytest.mark.parametrize("m,band,thr", [(128, 6, False), (128, 6, True),
                                        (2048, 102, True)])
def test_dtw_wavefront_compiles(one_chip, m, band, thr):
    """The single-query DTW (sequential and shard-local re-rank)."""
    c = 1000                                 # not a multiple of 128
    if thr:
        fn = lambda q, x, t: dtw_wavefront.dtw_wavefront(  # noqa: E731
            q, x, band, threshold=t)
        args = (_arg(one_chip, (m,)), _arg(one_chip, (c, m)),
                _arg(one_chip, (c,)))
    else:
        fn = lambda q, x: dtw_wavefront.dtw_wavefront(q, x, band)  # noqa: E731
        args = (_arg(one_chip, (m,)), _arg(one_chip, (c, m)))
    _compile_kernel(fn, *args)


@pytest.mark.parametrize("b,k", [(8, 20), (24, 40)])
def test_collision_count_batch_compiles(one_chip, b, k):
    """The engine's batched probe over a 2^20-row archive: L=20 band
    keys, or K=40 signature hashes times 3 multiprobe offsets."""
    _compile_kernel(collision_count.collision_count_batch,
                    _arg(one_chip, (b, k), jnp.int32),
                    _arg(one_chip, (1 << 20, k), jnp.int32))


@pytest.mark.parametrize("s", [3, 643])
def test_cs_tables_compiles(one_chip, s):
    """``"ssh-cs"`` default geometry (rows=4, width=4096) over one encode
    chunk; 3 and 643 shingles per row are lengths 128 and 2048."""
    _compile_kernel(lambda bk, sg: count_sketch.cs_tables(bk, sg, 4096),
                    _arg(one_chip, (256, 4, s), jnp.int32),
                    _arg(one_chip, (256, 4, s)))
