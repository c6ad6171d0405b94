"""The cold-start cell's control at a size a CPU test run holds: the plain
reference with float8 weights in the server's place comes out beyond the
cell's token-gap limit, while the server itself stays within it.

Published widths and vocabulary with 4 of 40 layers and short prompts:
the float8 error reaches the logits through the layers, and with fewer of
them the control can choose every token right."""

import copy

from chipbench import common, control

SEEDS = (1, 4)


def test_float8_control_is_not_correct_and_the_server_is():
    wl = copy.deepcopy(common.load_workload("minicpm2b-cold-start"))
    wl["config_spec"]["num_hidden_layers"] = 4
    wl["traffic_params"]["prompt_len"] = 32
    limit = wl["limits"]["token_gap"]
    got = list(control.readings(wl, SEEDS, require_tpu=False))
    assert [r["seed"] for r in got] == list(SEEDS)
    for r in got:
        assert r["program"]["token_gap"] <= limit, r
        assert r["control"]["token_gap"] > limit, r
