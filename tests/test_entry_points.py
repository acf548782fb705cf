"""The command-line serving smokes the Makefile runs (``serve-*`` targets)
and the kept examples, each run in-process to a clean exit."""

import runpy
import shlex
import tempfile
from pathlib import Path

import pytest

from repro.launch import serve

REPO = Path(__file__).resolve().parents[1]


def makefile_serve_argv(target: str) -> list:
    """The argv a Makefile ``serve-*`` target passes to the serving driver."""
    text = (REPO / "Makefile").read_text().replace("\\\n", " ")
    recipe = text.split(f"\n{target}:", 1)[1].split("\n\n", 1)[0]
    return shlex.split(recipe.split("-m repro.launch.serve", 1)[1])


@pytest.fixture
def run_state_in_tmp(tmp_path, monkeypatch):
    """Keep the compile cache, the autotune table and ``auto`` durability
    directories out of the checkout and out of other tests' way."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))


@pytest.mark.parametrize("target", ["serve-apsp", "serve-dynamic",
                                    "serve-chaos", "serve-chaos-correlated"])
def test_serve_smoke_exits_zero(target, run_state_in_tmp, capsys):
    assert serve.main(makefile_serve_argv(target)) == 0
    assert "[done]" in capsys.readouterr().out


@pytest.mark.parametrize("argv", [["--arch", "apsp"], ["--gen", "16"]],
                         ids=["arch", "gen"])
def test_serve_takes_no_model_options(argv, capsys):
    """The driver serves APSP only: the model-serving options are gone."""
    with pytest.raises(SystemExit) as e:
        serve.main(argv)
    assert e.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err


@pytest.mark.parametrize("name,says", [
    ("quickstart", ["all methods agree", "path witnesses its distance"]),
    ("batch_apsp", ["corpus: 8 graphs", "graph 7 (n=50)"]),
])
def test_example_runs(name, says, capsys):
    main = runpy.run_path(str(REPO / "examples" / f"{name}.py"))["main"]
    assert main() in (None, 0)
    out = capsys.readouterr().out
    for s in says:
        assert s in out
