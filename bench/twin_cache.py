"""A twin and its packed week, cached on disk inside the checkout.

A full-scale twin takes minutes of host time to generate and pack, and it
is the same in every run of a configuration: it is part of the
configuration, made from the dataset's own seed. The first run in a
checkout builds it with the program's generator and writes it here; later
runs read it back. The cache is keyed by the dataset name and a hash of
the generator and packing sources, so a change to either builds afresh.

Layout: ``bench/.cache/twin/<dataset>-<hash>/`` holds one ``.npy`` file
per array and ``meta.json`` for everything else. A build is written to a
sibling directory first and renamed into place, so a run that is cut
never leaves a half-written twin behind.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil

import numpy as np

#: Sources whose change changes the twin or its packing (relative to the
#: program's checkout).
SOURCES = (
    "src/repro/data/digital_twin.py",
    "src/repro/core/population.py",
    "src/repro/core/contact.py",
    "src/repro/configs/base.py",
    "src/repro/configs/epidemics.py",
)
CACHE_DIR = os.path.join("bench", ".cache", "twin")


def source_hash() -> str:
    from repro.data import digital_twin

    src = os.path.abspath(digital_twin.__file__)
    for _ in range(4):  # src/repro/data/digital_twin.py
        src = os.path.dirname(src)
    h = hashlib.sha256()
    for rel in SOURCES:
        with open(os.path.join(src, rel), "rb") as f:
            h.update(rel.encode() + b"\0" + f.read())
    return h.hexdigest()[:16]


def cache_path(root: str, dataset: str) -> str:
    return os.path.join(root, CACHE_DIR, f"{dataset}-{source_hash()}")


def _split(obj, prefix: str, arrays: dict) -> dict:
    """A dataclass as JSON-able fields, its arrays moved into ``arrays``."""
    meta = {}
    for f in dataclasses.fields(obj):
        if f.name.startswith("_"):
            continue
        v = getattr(obj, f.name)
        if isinstance(v, np.ndarray):
            arrays[f"{prefix}{f.name}"] = v
            meta[f.name] = {"array": f"{prefix}{f.name}"}
        elif isinstance(v, (np.integer, np.floating)):
            meta[f.name] = v.item()
        elif isinstance(v, (int, float, str)):
            meta[f.name] = v
    return meta


def _join(cls, meta: dict, load):
    return cls(**{k: load(v["array"]) if isinstance(v, dict) else v
                  for k, v in meta.items()})


def save(pop, block_size: int, path: str) -> None:
    arrays = {}
    meta = _split(pop, "pop.", arrays)
    meta["week"] = [_split(d, f"week{i}.", arrays)
                    for i, d in enumerate(pop.week)]
    meta["packed"] = [_split(d, f"packed{i}.", arrays)
                      for i, d in enumerate(pop.packed_week(block_size))]
    meta["block_size"] = block_size
    tmp = path + ".partial"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    for name, a in arrays.items():
        np.save(os.path.join(tmp, name + ".npy"), a)
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)
    shutil.rmtree(path, ignore_errors=True)
    os.replace(tmp, path)


def load(path: str):
    from repro.core import population as pop_lib

    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    get = lambda name: np.load(os.path.join(path, name + ".npy"))
    week = [_join(pop_lib.DayVisits, m, get) for m in meta.pop("week")]
    packed = [_join(pop_lib.PackedDayVisits, m, get)
              for m in meta.pop("packed")]
    block_size = meta.pop("block_size")
    pop = _join(pop_lib.Population, {**meta, "week": week}, get)
    pop._packed[block_size] = packed
    return pop


def load_or_build(root: str, dataset: str, block_size: int):
    """The twin of ``dataset`` with its week packed for ``block_size``.
    Returns ``(population, built)``; ``built`` is True where this call
    generated it (and wrote the cache)."""
    from repro.configs import get_epidemic

    path = cache_path(root, dataset)
    if os.path.exists(os.path.join(path, "meta.json")):
        return load(path), False
    pop = get_epidemic(dataset).build()
    pop.packed_week(block_size)
    save(pop, block_size, path)
    return pop, True
