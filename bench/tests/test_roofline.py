"""The yardstick's arithmetic against counts made by hand."""
import json
import os

import pytest

import roofline
import run

CONFIGS = os.path.join(roofline.HERE, "configs")


def config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


# conv1 2*32*32*25*3*32 = 4,915,200; conv2 2*16*16*25*32*64 = 26,214,400;
# fc 2*(4096*128 + 128*128 + 128*10) = 1,083,904; sum 32,213,504, x3.
# EMNIST: conv1 2*28*28*25*1*32 = 1,254,400; conv2 2*14*14*25*32*64 =
# 20,070,400; fc 2*3136*62 = 388,864; sum 21,713,664, x3.
@pytest.mark.parametrize("name,flops,params", [
    ("cifar10_cnn", 96_640_512, 595_914),
    ("emnist_cnn", 65_140_992, 246_590),
])
def test_cnn_counts(name, flops, params):
    cfg = config(name)
    model = run.load_model(cfg["model"])
    assert model.flops_per_sample(cfg) == flops == cfg["flops_per_sample"]
    assert model.param_count(cfg) == params == cfg["params"]


def test_cross_gram_cost():
    # U (10, D) and V (100, D) in float32, read once; the (10, 100) result
    # written once: 4 * (10 D + 100 D + 1000) bytes, 2 * 10 * 100 * D FLOPs
    flops, nbytes = roofline.cross_gram_cost(10, 100, 595_914)
    assert flops == 2 * 10 * 100 * 595_914
    assert nbytes == 4 * (110 * 595_914 + 1000)


def test_cross_gram_counts_only_hbm_operands():
    flops, nbytes = roofline.cross_gram_cost(20, 2000, 246_590, in_hbm=(False, True, False))
    assert flops == 2 * 20 * 2000 * 246_590
    assert nbytes == 4 * 2000 * 246_590


def test_cross_gram_is_bandwidth_bound_on_v5e():
    peak = roofline.peaks("TPU v5 lite")
    t, bound = roofline.roofline_seconds(*roofline.cross_gram_cost(10, 100, 595_914), peak)
    assert bound == "bandwidth"
    assert t == pytest.approx(4 * (110 * 595_914 + 1000) / 819e9)


def test_unknown_device_kind_fails():
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("TPU v99")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
