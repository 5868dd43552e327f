"""Each configuration end to end on the CPU at smoke widths and a few
tenants, through a one-second window, with the chip check skipped."""
import pytest

from conftest import run_smoke, smoke_cell


@pytest.mark.parametrize("name,config,traffic", [
    ("fleet48-internlm2.steady", "fleet48-internlm2-1.8b", "steady"),
    ("tabular512.backlog", "tabular512-baf", "backlog"),
    ("fleet48-internlm2.backlog", "fleet48-internlm2-1.8b", "backlog")])
def test_a_run_is_correct_and_reports_its_metrics(name, config, traffic):
    cell = smoke_cell(name, config, traffic)
    result = run_smoke(cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert result["info"]["window_compiles"] == 0
    assert result["info"]["stage_errors"] == 0
    assert list(result)[-1] == "checks"
    for number in result["checks"].values():
        assert number["value"] <= number["limit"]
