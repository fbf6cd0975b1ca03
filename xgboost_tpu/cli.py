"""Config-file driven CLI: train | dump | pred, plus telemetry tools.

Reference: ``src/cli_main.cc`` (CLITask :30-35, CLIParam :37) + the
key=value config parser (``src/common/config.h``). Usage:

    python -m xgboost_tpu <config> [key=value ...]
    python -m xgboost_tpu dispatch-report
    python -m xgboost_tpu trace-report <trace-file|glob> ... [--top N]
    python -m xgboost_tpu obs-report <run_dir> ... [--top-rounds N]
    python -m xgboost_tpu serve-report <run_dir> ... [--top N]
    python -m xgboost_tpu checkpoint-inspect <dir> [--json]
    python -m xgboost_tpu serve (--port N | --stdin) [--model name=path ...]
        [--deliver name=watch_dir ...] [--run-dir D] [--manifest F]
    python -m xgboost_tpu serve-fleet --port N --run-dir D [--replicas K]
        [--model name=path ...]
    python -m xgboost_tpu deliver --connect HOST:PORT --model M --watch DIR
        [--mode shadow|fraction] [--eval-npz F] | --status | --stop

Config keys mirror the reference: task, data, test:data, model_in,
model_out, model_dir, num_round, save_period, eval[name]=path, dump_format,
name_pred, plus any booster/learner parameters. ``trace-report``
summarizes Chrome trace-event files written via ``XGBTPU_TRACE``
(multiple/globbed inputs merge into one report: top spans by self time,
per-rank totals — ``docs/observability.md``). ``obs-report`` merges a
fleet run's per-rank observability (``run_dir/obs/rank<k>/``) into one
clock-aligned trace, a metrics rollup and a per-round fleet table
(``observability/fleet.py``). ``serve-report`` is its serving-plane
sibling: it merges a model server's ``run_dir/obs/server/`` access log,
dispatch flight ring and request trace into per-model latency
percentiles, a shed/degrade timeline, coalescing stats and a
worst-request exemplar table (``observability/serve_report.py``,
docs/serving.md "Tracing a request"). Both reports accept MULTIPLE
run_dirs — and a fleet run_dir with ``replica<k>/`` subdirs expands to
every replica — merging into one fleet-wide trace and a per-replica /
per-tenant rollup (docs/serving.md "Scaling out"). ``serve-fleet`` runs
that fleet: N supervised crash-only ``serve`` replicas sharing one
manifest behind the consistent-hash routing front
(``serving/fleet/``).
``dispatch-report`` prints the fully-resolved kernel dispatch table
(op × impl × reason: preferred/pinned/degraded/unavailable) for the
current platform, including any ``XGBTPU_DISPATCH`` pins and legacy
kill-switch envs in effect (docs/perf.md, "Choosing a kernel"); exit 1
when any op has no usable implementation.
``lint`` runs the static-analysis gate (trace-safety / retrace / dtype /
concurrency passes, ``docs/static_analysis.md``):

    python -m xgboost_tpu lint [paths...] [--baseline F] [--write-baseline]

``checkpoint-inspect`` lists a resume directory's checkpoints (round,
size, checksum-verify status) and marks the newest verified one — the
snapshot ``train(resume_from=...)`` / elastic replay would pick up
(``docs/resilience.md``). Exit status 1 when nothing verifies.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, List, Tuple

import numpy as np

from .data.dmatrix import DMatrix
from .learner import Booster
from .training import train as _train
from .utils import console_logger


def parse_config_file(path: str) -> List[Tuple[str, str]]:
    """key=value lines; '#' comments (reference src/common/config.h)."""
    out: List[Tuple[str, str]] = []
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            if "=" not in line:
                raise ValueError(f"bad config line: {line!r}")
            k, _, v = line.partition("=")
            out.append((k.strip(), v.strip().strip('"')))
    return out


_CLI_KEYS = {
    "task", "data", "test:data", "model_in", "model_out", "model_dir",
    "num_round", "save_period", "dump_format", "name_pred", "name_fmap",
    "name_dump", "fmap", "with_stats", "iteration_begin", "iteration_end",
    "silent",
}


def _split_params(pairs: List[Tuple[str, str]]):
    cli: Dict[str, str] = {}
    params: Dict[str, Any] = {}
    evals: List[Tuple[str, str]] = []  # (name, path)
    for k, v in pairs:
        if k.startswith("eval[") and k.endswith("]"):
            evals.append((k[5:-1], v))
        elif k in _CLI_KEYS:
            cli[k] = v
        else:
            params[k] = v
    return cli, params, evals


def cli_main(argv: List[str]) -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 1
    if argv[0] == "trace-report":
        from .observability.report import main as report_main

        return report_main(argv[1:])
    if argv[0] == "obs-report":
        from .observability.fleet import main as fleet_main

        return fleet_main(argv[1:])
    if argv[0] == "serve-report":
        from .observability.serve_report import main as serve_report_main

        return serve_report_main(argv[1:])
    if argv[0] == "lint":
        from .analysis.cli import main as lint_main

        return lint_main(argv[1:])
    if argv[0] == "dispatch-report":
        from .dispatch.report import main as dispatch_report_main

        return dispatch_report_main(argv[1:])
    if argv[0] == "checkpoint-inspect":
        return checkpoint_inspect_main(argv[1:])
    if argv[0] == "deliver":
        return deliver_main(argv[1:])
    if argv[0] == "serve":
        from .serving.server import serve_main

        return serve_main(argv[1:])
    if argv[0] == "serve-fleet":
        from .serving.fleet.supervisor import serve_fleet_main

        return serve_fleet_main(argv[1:])
    pairs = parse_config_file(argv[0])
    for extra in argv[1:]:
        k, _, v = extra.partition("=")
        pairs.append((k, v))
    cli, params, eval_specs = _split_params(pairs)
    task = cli.get("task", "train")

    if task == "train":
        from .config import enable_compile_cache

        enable_compile_cache()
        dtrain = DMatrix(cli["data"])
        evals = [(DMatrix(p), name) for name, p in eval_specs]
        evals.append((dtrain, "train"))
        num_round = int(cli.get("num_round", 10))
        save_period = int(cli.get("save_period", 0))
        model_dir = cli.get("model_dir", "")
        callbacks = []
        if save_period > 0:
            from .callback import TrainingCheckPoint

            callbacks.append(
                TrainingCheckPoint(model_dir or ".", name="", interval=save_period)
            )
        xgb_model = None
        if cli.get("model_in"):
            xgb_model = Booster(params, model_file=cli["model_in"])
        bst = _train(
            params, dtrain, num_boost_round=num_round, evals=evals,
            verbose_eval=not int(cli.get("silent", 0)),
            xgb_model=xgb_model, callbacks=callbacks,
        )
        out = cli.get("model_out", os.path.join(model_dir, f"{num_round:04d}.model")
                      if model_dir else f"{num_round:04d}.model.json")
        bst.save_model(out)
        console_logger.info(f"model saved to {out}")
    elif task == "dump":
        bst = Booster(params, model_file=cli["model_in"])
        fmap = cli.get("name_fmap", cli.get("fmap", ""))
        dump_format = cli.get("dump_format", "text")
        with_stats = bool(int(cli.get("with_stats", 0)))
        out = cli.get("name_dump", "dump.txt")
        bst.dump_model(out, fmap=fmap, with_stats=with_stats, dump_format=dump_format)
        console_logger.info(f"dump saved to {out}")
    elif task == "pred":
        bst = Booster(params, model_file=cli["model_in"])
        dtest = DMatrix(cli["test:data"])
        begin = int(cli.get("iteration_begin", 0))
        end = int(cli.get("iteration_end", 0))
        it_range = (begin, end) if (begin, end) != (0, 0) else None
        preds = bst.predict(dtest, iteration_range=it_range)
        out = cli.get("name_pred", "pred.txt")
        np.savetxt(out, np.asarray(preds), fmt="%.9g")
        console_logger.info(f"predictions saved to {out}")
    else:
        print(f"unknown task: {task}", file=sys.stderr)
        return 1
    return 0


def checkpoint_inspect_main(argv: List[str]) -> int:
    """``checkpoint-inspect <dir> [--json]``: the operator-facing read
    side of ``resume_from`` — what is on disk, what verifies, what a
    resume would actually load. ``--json`` emits the machine-readable
    form (one document: records + the newest-verified path) — the
    delivery controller's poll primitive, scriptable for operators
    (exit status semantics unchanged: 1 when nothing verifies)."""
    import json

    as_json = "--json" in argv
    argv = [a for a in argv if a != "--json"]
    if not argv or argv[0].startswith("-"):
        print("usage: python -m xgboost_tpu checkpoint-inspect <dir> "
              "[--json]", file=sys.stderr)
        return 1
    from .resilience.checkpoint import inspect_dir

    directory = argv[0]
    records = inspect_dir(directory)
    if as_json:
        newest = [r for r in records if r["newest_verified"]]
        # multi-rank dirs mark one newest-verified PER resume scope (the
        # top dir plus each rank<N>/); the top-level answer is the most
        # advanced verified snapshot across all of them, not whichever
        # scope happened to be listed last
        best = max(newest, key=lambda r: r["rounds"]) if newest else None
        print(json.dumps({
            "dir": directory,
            "records": records,
            "newest_verified": best["path"] if best else None,
            "newest_verified_rounds":
                best["rounds"] if best else None,
        }, indent=2))
        return 0 if best else 1
    if not records:
        print(f"{directory}: no checkpoints found")
        return 1
    print(f"{'':2} {'round':>8} {'bytes':>12} {'status':<40} path")
    any_ok = False
    for rec in records:
        mark = "*" if rec["newest_verified"] else " "
        status = "verified" if rec["verified"] else \
            f"CORRUPT: {rec['detail']}"
        any_ok = any_ok or rec["verified"]
        print(f"{mark:2} {rec['rounds']:>8} {rec['bytes']:>12} "
              f"{status:<40} {rec['path']}")
    print("\n'*' = newest verified (what train(resume_from=...) / "
          "elastic replay loads)")
    return 0 if any_ok else 1


def deliver_main(argv: List[str]) -> int:
    """``deliver``: the operator client for the serving ``deliver`` op —
    attach (or inspect/stop) a continuous train-to-serve delivery
    controller on a RUNNING server or fleet router over the JSONL
    protocol (docs/serving.md "Model delivery")::

        python -m xgboost_tpu deliver --connect HOST:PORT \\
            --model M --watch CKPT_DIR [--mode shadow|fraction]
            [--fraction F] [--min-requests N] [--bake-s S] [--poll-s S]
            [--dauc TOL] [--eval-npz FILE]
        python -m xgboost_tpu deliver --connect HOST:PORT --status
        python -m xgboost_tpu deliver --connect HOST:PORT --stop --model M
    """
    import json
    import socket

    usage = ("usage: python -m xgboost_tpu deliver --connect HOST:PORT "
             "(--model M --watch DIR [opts] | --status | --stop "
             "--model M)")
    msg: Dict[str, Any] = {"op": "deliver"}
    connect = None
    flags = {"--model": ("model", str), "--watch": ("watch", str),
             "--mode": ("mode", str), "--fraction": ("fraction", float),
             "--min-requests": ("min_requests", int),
             "--bake-s": ("bake_s", float), "--poll-s": ("poll_s", float),
             "--dauc": ("dauc_tol", float),
             "--p99-ratio": ("p99_ratio", float),
             "--from-rounds": ("from_rounds", int),
             "--eval-npz": ("eval_npz", str)}
    i = 0
    try:
        while i < len(argv):
            a = argv[i]
            if a == "--connect":
                i += 1
                connect = argv[i]
            elif a == "--status":
                msg["action"] = "status"
            elif a == "--stop":
                msg["action"] = "stop"
            elif a in flags:
                key, conv = flags[a]
                i += 1
                msg[key] = conv(argv[i])
            else:
                raise ValueError(f"unknown deliver option: {a!r}")
            i += 1
        if connect is None:
            raise ValueError("--connect HOST:PORT is required")
        if msg.get("action", "start") == "start" \
                and not (msg.get("model") and msg.get("watch")):
            raise ValueError("starting a delivery needs --model and "
                             "--watch")
        host, _, port = connect.rpartition(":")
        port = int(port)
    except (ValueError, IndexError) as e:
        print(f"deliver: {e}", file=sys.stderr)
        print(usage, file=sys.stderr)
        return 1
    try:
        with socket.create_connection((host or "127.0.0.1", port),
                                      timeout=30) as s:
            fh = s.makefile("rw", encoding="utf-8")
            fh.write(json.dumps(msg) + "\n")
            fh.flush()
            line = fh.readline()
    except OSError as e:
        print(f"deliver: cannot reach {connect}: {e}", file=sys.stderr)
        return 1
    try:
        resp = json.loads(line)
    except ValueError:
        print(f"deliver: bad response: {line!r}", file=sys.stderr)
        return 1
    print(json.dumps(resp, indent=2))
    return 0 if not resp.get("error") else 1


def main() -> None:  # console entry
    sys.exit(cli_main(sys.argv[1:]))
