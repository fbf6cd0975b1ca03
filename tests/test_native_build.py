"""The on-demand native builds of processes that share a checkout (a test
run's workers): one builds, the others wait for it and load what it built,
and no one ever sees a library another's linker is still writing."""

import os
import shutil
import subprocess
import threading

import pytest

from xgboost_tpu import native


def test_concurrent_builds_build_once_and_install_whole(tmp_path,
                                                        monkeypatch):
    if not shutil.which("g++"):
        pytest.skip("no g++")
    src = str(tmp_path / "t.cpp")
    so = str(tmp_path / "libt.so")
    with open(src, "w") as f:
        f.write('extern "C" int one() { return 1; }\n')
    runs, seen = [], []
    real_run = subprocess.run

    def run(cmd, **kw):
        if cmd and cmd[0] == "g++":
            out = cmd[cmd.index("-o") + 1]
            runs.append(out)
            # the library's own path is never the linker's output
            assert out != so
            seen.append(os.path.exists(so))
        return real_run(cmd, **kw)

    monkeypatch.setattr(subprocess, "run", run)
    ok = []
    threads = [threading.Thread(target=lambda: ok.append(
        native._compile(src, so, ["-O0"]))) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ok == [True] * 4
    assert len(runs) == 1 and seen == [False]
    assert os.path.getsize(so) > 0
    leftovers = [p for p in os.listdir(tmp_path) if ".part." in p]
    assert not leftovers
    # a failed build leaves no partial file and reports the failure
    with open(src, "w") as f:
        f.write("this is not C++\n")
    assert not native._compile(src, so, ["-O0"])
    assert not [p for p in os.listdir(tmp_path) if ".part." in p]
