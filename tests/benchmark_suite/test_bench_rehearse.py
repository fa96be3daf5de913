"""Chipless compile of a training step for a described v5e (the rehearsal
`benchmarks/rehearse.py` makes at real size), at a small size: the flash
kernels are in the TPU program, two Mosaic calls a layer (the forward and
the one backward kernel), and data parallelism over four chips brings its
all-reduces.  One file, topology in a fixture
(`on-chip-measurement` section 2)."""

import pytest

import bench_suite_util  # noqa: F401

SMALL = {"family": "transformer_lm", "vocab_size": 1024, "hidden_size": 256,
         "ffn_dim": 1024, "num_attention_heads": 2, "num_hidden_layers": 2,
         "max_position_embeddings": 1024, "init_std": 0.02,
         "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9,
                   "wd": 0.0, "multi_precision": True,
                   "sequence_length": 1024, "per_chip_batch": 2}}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture()
def no_persistent_cache():
    # a chipless compile is written to the persistent cache but cannot be
    # read back without a chip
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.mark.parametrize("chips", [1, 4])
def test_the_step_compiles_for_the_described_chip(topo, no_persistent_cache,
                                                  chips):
    from benchmarks import rehearse
    out = rehearse.step_memory(SMALL, chips, topo)
    assert out["mosaic_calls"] == 2 * SMALL["num_hidden_layers"]
    assert out["argument_size_in_bytes"] > 0
    if chips == 1:
        assert out["all_gathers"] == out["all_reduces"] == 0
    else:
        assert out["all_reduces"] > 0
