"""The numbers a training cell compares: a fault confined to one kind
of leaf passes the median of all leaves and fails its own kind's."""
import pytest

from perfbench import train_check

KINDS = dict({"conv%d" % i: "conv_w" for i in range(6)},
             **{"gamma%d" % i: "bn_gamma" for i in range(3)})


def _side(gamma_change=0.02):
    norms = dict({k: 1.0 for k in KINDS if k.startswith("conv")},
                 **{k: 2.0 for k in KINDS if k.startswith("gamma")})
    change = dict({k: 0.01 for k in KINDS if k.startswith("conv")},
                  **{k: gamma_change for k in KINDS
                     if k.startswith("gamma")})
    return train_check.readings([7.0, 6.9, 6.8],
                                {k: 0.01 * v for k, v in norms.items()},
                                0.01, change)


def test_sound_sides_read_nought_for_every_kind():
    values = train_check.numbers(_side(), _side(), KINDS)
    assert set(values) >= {"loss_gap", "grad_gap", "change_gap_median",
                           "change_gap.conv_w",
                           "change_gap_median.bn_gamma"}
    assert all(v == 0.0 for v in values.values())


def test_unmoved_gammas_fail_their_kind_and_pass_the_median_of_all():
    values = train_check.numbers(_side(gamma_change=0.0), _side(), KINDS)
    assert values["change_gap_median"] == 0.0
    assert values["change_gap_median.bn_gamma"] == pytest.approx(1.0)
    assert values["change_gap.conv_w"] == 0.0
    judged = train_check.judge(values, {"change_gap_median": 0.02,
                                        "change_gap_median.bn_gamma": 0.08})
    assert judged["change_gap_median.bn_gamma"]["value"] > 0.08


def test_without_kinds_only_the_whole_model_numbers_are_given():
    values = train_check.numbers(_side(), _side())
    assert set(values) == {"loss_gap", "grad_gap", "grad_gap_median",
                           "change_gap", "change_gap_median"}
