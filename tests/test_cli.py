"""The command table of ``python -m xgboost_tpu`` (ISSUE 28): every command
the module docstring names answers ``--help`` with its usage and without a
traceback, and the two report commands of the deleted measurement stack are
commands no longer."""

import pytest

from xgboost_tpu import cli
from xgboost_tpu.cli import cli_main

COMMANDS = ("trace-report", "obs-report", "serve-report", "lint",
            "dispatch-report", "checkpoint-inspect", "deliver", "serve",
            "serve-fleet")


@pytest.mark.parametrize("cmd", COMMANDS)
def test_command_is_documented_and_answers_help(cmd, capsys):
    assert f"python -m xgboost_tpu {cmd}" in cli.__doc__
    try:
        rc = cli_main([cmd, "--help"])
    except SystemExit as e:  # argparse's own --help
        rc = e.code
    # 0 where --help is an option, 1 with the usage where it is not one
    assert rc in (0, 1), rc
    out, err = capsys.readouterr()
    assert f"python -m xgboost_tpu {cmd}" in out + err
    assert "Traceback" not in out + err


@pytest.mark.parametrize("which", ["grow", "perf"])
def test_deleted_report_is_no_command(which, tmp_path, monkeypatch):
    cmd = which + "-report"
    assert cmd not in cli.__doc__
    # a first argument that names no command is a config file's path
    monkeypatch.chdir(tmp_path)
    with pytest.raises(FileNotFoundError):
        cli_main([cmd])
