"""chip_smoke.py can never pass without a chip: under the CPU backend
``main()`` exits non-zero naming the backend it found, and prints no
result line. The line a pass ends on has the driver's keys and no
others."""

import json

import pytest

import chip_smoke


def test_refuses_the_cpu_backend_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "'cpu'" in str(e.value.code)
    assert "JAX_PLATFORMS" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_result_line_has_exactly_the_contract_keys():
    got = json.loads(chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    ))
    assert got == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
    }}
