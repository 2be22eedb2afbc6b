"""Find configurations, traffic mixes, drivers, network builders and
metric readers by the names the manifest gives them."""

import importlib.util
import json
import pathlib
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _path(root, kind: str, name: str, suffix: str) -> pathlib.Path:
    if not NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = pathlib.Path(root) / "bench" / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return path


def data(root, kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``."""
    return json.loads(_path(root, kind, name, ".json").read_text())


def module(root, kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module of its own."""
    path = _path(root, kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def manifest(root) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())
