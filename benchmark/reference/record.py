#!/usr/bin/env python3
"""Record the plain reference's answer into a configuration file.

    python3 benchmark/reference/record.py benchmark/configs/<name>.json

Explores the configuration's model with its plain reference and writes
``expect`` (unique states, depth, discovery depths) and ``waves`` (the
per-wave counts the roofline work functions read) into the file. Runs
on the host CPU alone; ``paxos-4c3s`` takes several minutes.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from reference import bfs  # noqa: E402


def main(path):
    with open(path) as fh:
        cfg = json.load(fh)
    import importlib

    model = importlib.import_module(f"reference.{cfg['reference']}")
    t0 = time.monotonic()
    ans = bfs.explore(model, cfg["params"])
    cfg["expect"] = dict(unique=ans["unique"], depth=ans["depth"],
                         discoveries=ans["discoveries"])
    cfg["waves"] = ans["waves"]
    with open(path, "w") as fh:
        json.dump(cfg, fh, indent=1)
        fh.write("\n")
    print(f"{cfg['name']}: {ans['unique']} states, depth {ans['depth']}, "
          f"{sorted(ans['discoveries'])}, {time.monotonic() - t0:.1f} s")


if __name__ == "__main__":
    main(sys.argv[1])
