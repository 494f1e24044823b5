"""Public API surface gate (runs in the CI ``lint`` job).

Snapshots the public serving API — ``repro.service.__all__`` plus the shim
modules ``repro.serve`` / ``repro.stream`` — into
``tools/api_surface.json`` and fails when the live surface drifts from the
checked-in snapshot.  A rename, removal, or new export must land together
with a reviewed snapshot update (``--update``), so the serving API can
never change silently under downstream users.

Each ``__all__`` name is also resolved with ``getattr`` — an export that
doesn't import is a failure, not a snapshot diff.

Run:   PYTHONPATH=src python tools/check_api_surface.py
       PYTHONPATH=src python tools/check_api_surface.py --update
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, os.path.join(str(ROOT), "src"))

#: the reviewed serving surface: the typed API, the HTTP gateway over it,
#: both shim packages, the crash-consistency layer, and the program spans
#: an operator switches on to take a profile
MODULES = ["repro.service", "repro.gateway", "repro.learn", "repro.serve",
           "repro.stream", "repro.stream.checkpoint", "repro.utils.spans"]

SNAPSHOT = ROOT / "tools" / "api_surface.json"


def live_surface() -> dict[str, list[str]]:
    surface: dict[str, list[str]] = {}
    for mod_name in MODULES:
        mod = importlib.import_module(mod_name)
        names = getattr(mod, "__all__", None)
        if names is None:
            raise SystemExit(f"FAIL {mod_name}: no __all__ (unreviewable surface)")
        for name in names:
            try:
                getattr(mod, name)
            except AttributeError as exc:
                raise SystemExit(
                    f"FAIL {mod_name}.{name}: listed in __all__ but does not "
                    f"resolve ({exc})"
                ) from exc
        surface[mod_name] = sorted(names)
    return surface


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--update", action="store_true",
                    help="rewrite the snapshot to the live surface "
                         "(do this in the same PR as the API change)")
    args = ap.parse_args(argv)

    surface = live_surface()
    if args.update:
        SNAPSHOT.write_text(json.dumps(surface, indent=1) + "\n")
        print(f"api surface snapshot updated ({SNAPSHOT.relative_to(ROOT)})")
        return 0

    if not SNAPSHOT.exists():
        print(f"FAIL: snapshot missing — run: python {Path(__file__).name} --update")
        return 1
    recorded = json.loads(SNAPSHOT.read_text())
    failed = False
    for mod_name in sorted(set(recorded) | set(surface)):
        old = set(recorded.get(mod_name, []))
        new = set(surface.get(mod_name, []))
        for name in sorted(new - old):
            print(f"FAIL {mod_name}: unreviewed new export '{name}'")
            failed = True
        for name in sorted(old - new):
            print(f"FAIL {mod_name}: export '{name}' removed from the surface")
            failed = True
    if failed:
        print("api surface drift — review the change, then run "
              "`python tools/check_api_surface.py --update` in the same PR")
        return 1
    total = sum(len(v) for v in surface.values())
    print(f"api surface OK ({len(surface)} modules, {total} exports)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
