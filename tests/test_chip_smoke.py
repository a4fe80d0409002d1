"""chip_smoke.check_stats: the one function that decides whether the
DEVICE answered, held to recorded OP_STATS snapshots.

``GOOD`` is the snapshot of a full-width ``chip_smoke.py`` run (3 rounds
against ``--committee 100 --warm 128 --warm-rlc``), cut to the sections
the check reads, with the device section as a one-chip v5e reports it.
Every way a sidecar can answer without the chip must fail the check.
"""

import copy

import pytest

import chip_smoke

GOOD = {
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    "paths": {"per_sig": 9, "rlc": 18, "rlc_bisect": 6},
    "compile": {"hits": 0, "misses": 10, "warm_boot": False,
                "warmup_wall_s": 234.298,
                "shapes": {"rlc:128": 44.84, "rlc:16": 20.741,
                           "rlc:32": 39.18, "rlc:64": 46.177,
                           "rlc:8": 22.997, "warmup:128": 10.93,
                           "warmup:16": 9.337, "warmup:32": 19.164,
                           "warmup:64": 12.27, "warmup:8": 8.621}},
    "guard": {"busy_replies": 0, "canary_failures": 0, "canary_passes": 0,
              "device_ok": True, "host_fallback_records": 0,
              "late_completions": 0, "poison_host_verified": 0,
              "rebooting": False, "reboots": 0, "warm_boot": False,
              "wedges": 0, "wedges_by_key": {}},
}
EXPECT = dict(count=1, rlc_path="rlc", rlc_launches=18, warmed_shapes=10)


def _with(path, value):
    stats = copy.deepcopy(GOOD)
    node = stats
    for key in path[:-1]:
        node = node[key]
    if value is None:
        del node[path[-1]]
    else:
        node[path[-1]] = value
    return stats


@pytest.mark.parametrize("stats,expect,fails_on", [
    (GOOD, EXPECT, None),
    (_with(("device", "platform"), "cpu"), EXPECT, "device.platform"),
    (_with(("paths", "host"), 1), EXPECT, "host entry"),
    (_with(("guard", "host_fallback_records"), 67), EXPECT,
     "guard.host_fallback_records"),
    (_with(("guard", "wedges"), 1), EXPECT, "guard.wedges"),
    (_with(("device",), None), EXPECT, "no `device` section"),
    (_with(("guard", "device_ok"), False), EXPECT, "guard.device_ok"),
    (_with(("guard", "poison_host_verified"), 1), EXPECT,
     "guard.poison_host_verified"),
    # >=16-vote batches that took the per-signature ladder instead
    (_with(("paths",), {"per_sig": 27}), EXPECT, "paths['rlc']"),
    (_with(("compile", "misses"), 9), EXPECT, "warmed shapes"),
    (_with(("device", "count"), 4), EXPECT, "device.count"),
    # the mesh phase's expectations against a one-chip sidecar
    (GOOD, dict(count=4, rlc_path="rlc_sharded", rlc_launches=2,
                warmed_shapes=None), "device.count"),
])
def test_check_stats(stats, expect, fails_on):
    bad = chip_smoke.check_stats(stats, **expect)
    if fails_on is None:
        assert bad == []
    else:
        assert any(fails_on in line for line in bad), bad


def test_workload_is_a_function_of_the_seed_and_plants_its_forgeries():
    """Same seed, same bytes; the planted rows — and only those — are
    what the host reference rejects."""
    validators = chip_smoke.make_validators(3, 8)
    assert validators == chip_smoke.make_validators(3, 8)
    assert validators != chip_smoke.make_validators(4, 8)
    cert = chip_smoke.certificate(validators, [b"\x05" * 32] * 6)
    assert chip_smoke.reference_mask(cert) == [True] * 6
    for tamper in (chip_smoke.forge_vote, chip_smoke.wrong_key):
        mask = chip_smoke.reference_mask(tamper(cert, 4))
        assert mask == [i != 4 for i in range(6)]
    assert [chip_smoke.quorum(n) for n in (4, 10, 20, 50, 100, 1000)] \
        == [3, 7, 14, 34, 67, 667]
