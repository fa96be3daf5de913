"""The plain references against the system, tiny, on the CPU: float32 on
both sides, so only the order of the arithmetic differs."""

import jax
import numpy as np
import pytest

import bench_suite_util  # noqa: F401
from benchmarks import compare
from benchmarks.models import common as models_common
from benchmarks.reference import common as ref_common

LM = {"family": "transformer_lm", "vocab_size": 128, "hidden_size": 64,
      "ffn_dim": 256, "init_std": 0.02, "num_attention_heads": 4,
      "num_hidden_layers": 3, "max_position_embeddings": 64,
      "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9, "wd": 0.0,
                "multi_precision": False, "sequence_length": 64,
                "per_chip_batch": 4}}
RESNET = {"family": "resnet", "zoo_name": "resnet50_v1", "classes": 10,
          "image_size": 64, "stem_channels": 64, "units": [3, 4, 6, 3],
          "stage_channels": [256, 512, 1024, 2048],
          "train": {"optimizer": "sgd", "lr": 0.01, "momentum": 0.9,
                    "wd": 0.0001, "multi_precision": False,
                    "per_chip_batch": 32}}


# float32 on both sides.  The LM agrees to rounding over three steps.  In
# the ResNet fifty layers of batch norm over a few small images amplify
# rounding (float64 sides with the program, not with this reference): a
# percent in the first gradient, and chaos after it, so one step
@pytest.mark.parametrize("cfg, rows_per_block, steps, tol", [
    (LM, 2, 3, 1e-4), (RESNET, None, 1, 2e-2)], ids=["lm", "resnet"])
def test_first_steps_agree_with_the_plain_reference(cfg, rows_per_block,
                                                    steps, tol):
    import importlib
    family = importlib.import_module("benchmarks.models." + cfg["family"])
    train, seed = cfg["train"], 2 ** 31 + 5
    table = family.reference.param_table(cfg)
    batches = family.batches(cfg, seed, steps, train["per_chip_batch"])
    net, loss = family.build(cfg)
    names = models_common.seeded_net(
        net, table, ref_common.init_params(table, seed))
    trainer = models_common.make_trainer(net, loss, train,
                                         jax.devices()[:1])
    got = {"losses": []}
    to_ref = {prog: ref for ref, prog in names.items()}
    for i, (x, y) in enumerate(batches):
        got["losses"].append(float(trainer.fit_batch(x, y)))
        if i == 0:
            mom = {n: trainer._opt_state[n][0] for n in trainer.param_names}
            got["first_update_norms"] = ref_common.leaf_norms(mom)
            first = {to_ref[n]: np.asarray(a) for n, a in mom.items()}
    dist = ref_common.distance_from_init(
        table, seed, {to_ref[n]: trainer._params[n]
                      for n in trainer.param_names})
    got["total_update_norms"] = {names[r]: v for r, v in dist.items()}

    with jax.default_matmul_precision("highest"):
        ref = ref_common.follow_steps(
            lambda p, x, y: family.reference.loss_sum(p, cfg, x, y),
            ref_common.init_params(table, seed), batches,
            {"lr": train["lr"], "momentum": train["momentum"],
             "wd": train["wd"]},
            lambda p: ref_common.distance_from_init(table, seed, p),
            rows_per_block=rows_per_block, first_update=first)
    numbers = compare.training_numbers(got, ref, names)
    for name, (value, detail) in numbers.items():
        # the difference of the updates is first order in a rounding
        # error where a norm's gap is second order
        limit = 10 * tol if name == "first_update_difference" else tol
        assert value <= limit, (name, value, detail)


def test_worst_leaf_gap_is_measured_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "tiny": 1e-9}
    got = {"a": 1.0, "b": 2.2, "tiny": 5e-9}
    gap, where, rms = compare.leaf_gaps(got, ref)
    # the all-but-zero leaf moves by 4e-9 of a median leaf of 1.0
    assert gap == pytest.approx(0.1) and where.startswith("b:")
    assert rms == pytest.approx(0.1 / 3 ** 0.5)
    gap, where, _ = compare.leaf_gaps(dict(got, a=float("nan")), ref)
    assert gap != gap and where.startswith("a:")


def test_seeds_past_2_to_the_31_give_distinct_weights():
    table = {"w": ((4, 4), ("normal", 1.0))}
    a = ref_common.init_params(table, 7)["w"]
    b = ref_common.init_params(table, 2 ** 31 + 7)["w"]
    c = ref_common.init_params(table, 2 ** 31 + 7)["w"]
    assert not (a == b).all() and (b == c).all()
