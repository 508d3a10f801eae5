"""The comparison fails under each control and fault the cells can have,
with the rest of a run driven as usual (off the chip, at the tiny size).

The exchange between chips has no fault here: every cell runs on one chip.
"""
import pytest

import control
import tiny

CASES = [
    # (control or fault, cast, a compared number it must fail)
    ("bf16_emul", "unicast", "profile_fires"),
    ("analytic", "unicast", "noc_avg_latency"),
    ("analytic", "multicast", "noc_avg_latency"),
    ("state_unchanged", "unicast", "profile_trace"),
    ("half_batch", "unicast", "noc_num_noc_spikes"),
    ("half_batch", "multicast", "noc_num_noc_spikes"),
    ("alter_partition", "multicast", "partition_objective"),
    ("alter_placement", "unicast", "placement_avg_hop"),
    ("alter_replay", "multicast", "noc_avg_latency"),
]


# On the tiny random network under multicast: (fault, a number it must fail).
RANDOM_CASES = [
    ("half_batch", "noc_num_noc_spikes"),
    ("alter_replay", "noc_avg_latency"),
]


# Under the tiny core schedule: (control or fault, a number it must fail).
FAULT_CASES = [
    ("bf16_emul", "profile_fires"),
    ("state_unchanged", "profile_trace"),
    ("analytic", "noc_avg_latency"),
    ("half_batch", "noc_num_noc_spikes"),
    ("alter_replay", "noc_avg_latency"),
    ("drop_delivered", "noc_spikes_dropped"),
    ("detour_dropped", "noc_spikes_dropped"),
    ("detour_xy", "noc_per_link_hops"),
    ("dead_core_kept", "remap_invalid"),
    ("boundary_moved", "fault_segments"),
    ("migrated_off", "remap_migrated"),
]


@pytest.mark.parametrize("name,cast,number", CASES,
                         ids=[f"{c[0]}-{c[1]}" for c in CASES])
def test_control_is_not_correct(name, cast, number):
    with control.CONTROLS[name]():
        res = tiny.run(cast)
    assert not res["correct"]
    assert number in control.failing(res)


@pytest.mark.parametrize("name,number", RANDOM_CASES,
                         ids=[c[0] for c in RANDOM_CASES])
def test_control_is_not_correct_on_random_layers(name, number):
    with control.CONTROLS[name]():
        res = tiny.run("multicast", network=tiny.RANDOM)
    assert not res["correct"]
    assert number in control.failing(res)


@pytest.mark.parametrize("name,number", FAULT_CASES,
                         ids=[c[0] for c in FAULT_CASES])
def test_control_is_not_correct_under_faults(name, number):
    with control.CONTROLS[name]():
        res = tiny.run(faults=tiny.CORES)
    assert not res["correct"]
    assert number in control.failing(res)


def test_three_pass_sums_are_exact_on_the_weight_grid():
    """Why ``high`` is no control: the 2^-20 grid weights of the tiny net
    split exactly into two bf16 parts, so three-pass sums change nothing."""
    with control.CONTROLS["high_emul"]():
        res = tiny.run("unicast")
    assert res["correct"]
