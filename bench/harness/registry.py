"""Find a cell's pieces by name, so that new ones are new files.

Layout under the benchmark directory ``bench/``:

  * ``configs/<config>.json``  one configuration each;
  * ``traffic/<mix>.json``     one traffic mix each (parameters only);
  * ``generators/<name>.py``   one traffic generator each, named by the
    mix's ``generator`` key;
  * ``senders/<law>.py``       one sender law each, named by the mix's
    ``senders`` key;
  * ``drivers/<driver>.py``    one entry-point family each, named by the
    configuration's ``driver`` key;
  * ``reference/<name>.py``    a plain reference, named by ``reference``;
  * ``metrics/<metric>.py``    one reader per per-layer metric.

``BENCHMARK.json`` at the repository root names the cells and metrics.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class Registry:
    """Name lookups over one benchmark directory and its BENCHMARK.json."""

    def __init__(self, bench_dir: Path = BENCH_DIR,
                 benchmark_json: Path | None = None):
        self.dir = Path(bench_dir)
        path = benchmark_json or self.dir.parent / "BENCHMARK.json"
        self.spec = json.loads(Path(path).read_text())

    def cell(self, name: str) -> Dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        return json.loads((self.dir / "configs" / f"{name}.json").read_text())

    def traffic(self, name: str) -> Dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def module(self, kind: str, name: str) -> ModuleType:
        """Load ``<kind>/<name>.py``; names may hold dots."""
        path = self.dir / kind / f"{name}.py"
        key = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
        if key in sys.modules:
            return sys.modules[key]
        spec = importlib.util.spec_from_file_location(key, path)
        if spec is None or not path.exists():
            raise FileNotFoundError(f"no {kind} file {path}")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
        return mod

    def metrics_for(self, cell: str, group: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports:
        those without a ``workloads`` key, and those that list it."""
        return [m for m in self.spec[group]
                if "workloads" not in m or cell in m["workloads"]]
