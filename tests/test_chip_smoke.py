"""``chip_smoke.py`` rehearsed on the CPU: every phase in-process at tiny
sizes (Pallas in interpret mode), holding the same parity the chip run
holds, and the device check refusing the CPU."""

import json

import jax
import pytest

import chip_smoke


@pytest.fixture(scope="module")
def phase_a():
    return chip_smoke.phase_a(n_members=8, n_events=800, inc_chunk=200)


def test_phase_a_batch_and_incremental_match_oracle(phase_a):
    # the phase raises on any mismatch; here it must also have decided
    assert len(phase_a["res"].order) > 400


def test_phase_b_forked_body_matches_oracle(capsys):
    chip_smoke.phase_b(n_members=8, n_events=800, n_forkers=2)
    assert "[B] run_consensus xla forked" in capsys.readouterr().out


def test_phase_c_streaming_prefix_matches_oracle(capsys):
    chip_smoke.phase_c(n_members=16, n_events=1500, chunk=256,
                       n_oracle=1000, n_batch=1500)
    out = capsys.readouterr().out
    assert "[C] cut: streaming 1500" in out
    line = next(x for x in out.splitlines() if "StreamingConsensus" in x)
    assert "parity=True" in line
    assert "oracle_decided=0 " not in line and "batch_decided=0 " not in line


def test_phase_d_pallas_interpret_matches_phase_a(phase_a, capsys):
    chip_smoke.phase_d(phase_a, compiled=False)
    out = capsys.readouterr().out
    assert "kernels=pallas-interpret" in out
    assert "bmm_fallbacks=0" in out and out.count("parity=True") == 2


def test_four_chip_paths_on_virtual_devices(capsys):
    chip_smoke.four_chips(n_devices=4, n_members=16, n_events=1500,
                          chunk=256, n_oracle=1500, batch_members=8,
                          batch_events=800)
    out = capsys.readouterr().out
    assert out.count("parity=True") == 2


def test_mismatch_checks_name_the_field(phase_a):
    res = phase_a["res"]
    other = type(res)(**{**vars(res), "order": res.order[::-1]})
    assert chip_smoke.result_mismatch(res, res) == []
    assert chip_smoke.result_mismatch(res, other) == ["order"]
    ids = phase_a["packed"].ids.__getitem__
    order = [ids(i) for i in res.order]
    assert chip_smoke.prefix_mismatch(order[:10], res.round[:50], ids,
                                      res) == []
    assert chip_smoke.prefix_mismatch(order[1:11], res.round[:50] + 1, ids,
                                      res) == ["order", "round"]


def test_device_check_refuses_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.device_line(jax.devices())
    assert e.value.code not in (0, None) and "'cpu'" in str(e.value.code)
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_device_line_shape():
    class Tpu:
        platform, device_kind = "tpu", "TPU v5 lite"

    line = json.loads(chip_smoke.device_line([Tpu()]))
    assert line == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
