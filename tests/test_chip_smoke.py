"""chip_smoke.py's body at toy size on CPU, and its refusal of a CPU.

The chip run itself happens through the builder's chip tool; tier-1
proves the script still drives every phase — REST/worker/CLI job plane,
the cold-then-warm fleet, the four-device parity arm and the kernel
agreement — so a refactor that breaks an entry point it calls fails
here, not in a chip call.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = dict(
    services=16, hist_len=64, warm_ticks=2, kernel_batch=16,
    kernel_hist=(64,), kernel_interpret=True,
)


def _run_toy(tmp_path, **sizes):
    report = chip_smoke.run(
        chip_smoke.Sizes(**TOY, **sizes), check_device=False,
        out_dir=str(tmp_path),
    )
    with open(tmp_path / "chip_smoke.json") as fh:
        assert json.load(fh)["phases"].keys() == report["phases"].keys()
    return report


def test_smoke_body_runs_every_phase_on_cpu(tmp_path):
    """All four phases, device check injected off: Phase C on 4 of the
    8 virtual devices, kernels interpreted. The joint share is left to
    the slow variant below (its ~100 toy compiles alone outrun this
    test's time budget; tests/test_joint_fast_tick.py covers the joint
    columnar path in tier-1)."""
    report = _run_toy(tmp_path, joint_services=0)
    phases = report["phases"]
    assert list(phases) == ["A", "B", "C", "D"]
    assert phases["B"]["mesh"] is None
    assert phases["B"]["univariate"]["compiles"]["warm"] == 0
    assert phases["C"]["mesh"] == {"data": 4, "model": 1}
    assert phases["C"]["scalar_all_reduces_by_program"]["NONE"] == 0
    assert phases["D"]["interpret"] is True


@pytest.mark.slow
def test_smoke_body_joint_share_on_cpu(tmp_path):
    kinds = _run_toy(tmp_path, joint_services=8)["phases"]["B"]["joint"][
        "fast_path_docs"
    ]
    assert kinds["bivariate"] >= 2 and kinds["lstm"] >= 2


def test_script_entry_refuses_a_cpu(tmp_path, capsys):
    """`JAX_PLATFORMS=cpu python chip_smoke.py` must fail: the entry
    point exits non-zero (SystemExit with a message is exit status 1),
    names the platform it found, prints no result line and writes
    nothing."""
    with pytest.raises(SystemExit) as refused:
        chip_smoke.main(["--out", str(tmp_path / "out")])
    assert isinstance(refused.value.code, str), refused.value.code
    assert "'cpu'" in refused.value.code
    assert '"ok"' not in capsys.readouterr().out
    assert not (tmp_path / "out").exists()
