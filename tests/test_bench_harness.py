"""benchmarks/run.py reports a failed or unknown section in its exit code."""
import sys
import types

import pytest

from benchmarks import run as bench_run


def _raises():
    raise RuntimeError("section broke")


@pytest.mark.parametrize("section,expected", [
    ("nosuch", 1),
    ("table2", 1),        # bench_memory replaced by a module that raises
])
def test_failed_section_exits_nonzero(monkeypatch, section, expected):
    monkeypatch.setitem(sys.modules, "benchmarks.bench_memory",
                        types.SimpleNamespace(run=_raises))
    monkeypatch.setattr(sys, "argv", ["run", section])
    assert bench_run.main() == expected


def test_passing_sections_exit_zero(monkeypatch, capsys):
    monkeypatch.setitem(sys.modules, "benchmarks.bench_memory",
                        types.SimpleNamespace(run=lambda: ["a,1"]))
    monkeypatch.setattr(sys, "argv", ["run", "table2"])
    assert bench_run.main() == 0
    assert "a,1" in capsys.readouterr().out
