#!/usr/bin/env python3
"""The one process of a run that touches JAX and holds the chip.

Started by ``run.py`` -- through the launcher where the traffic file's
``entry`` is ``kfrun``, directly otherwise -- it drives the loop the
traffic file names and writes the run's facts to ``<out_dir>/run.json``
for the parent to make the metrics from.
"""

from __future__ import annotations

import time

T_WORKER = time.time()  # this process's start, for the parts of set-up

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True)
    a = ap.parse_args(argv)
    with open(a.spec) as f:
        spec = json.load(f)

    from kfbench.lib import files

    facts = files.load_module("loops", spec["traffic"]["loop"]).run(spec)
    facts["t_worker"] = T_WORKER
    tmp = os.path.join(spec["out_dir"], "run.json.tmp")
    with open(tmp, "w") as f:
        json.dump(facts, f)
    os.replace(tmp, os.path.join(spec["out_dir"], "run.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
