"""A configuration's matrix: made by its generator from the configuration's
own seed, and kept as npz under the checkout so that later runs load it."""
from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

from bench.lib.csr import CSR


def load(registry, config: dict, cache_dir: Path) -> CSR:
    gen = registry.module("gen", config["generator"])
    # the file name follows the sizes and the generator's source, so an
    # edited configuration never reads a stale matrix
    key = hashlib.sha1((json.dumps(config["params"], sort_keys=True)
                        + Path(gen.__file__).read_text()).encode())
    path = cache_dir / f"{config['name']}-{key.hexdigest()[:12]}.npz"
    if path.is_file():
        return CSR.load(path)
    m = gen.generate(config["params"])
    cache_dir.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp.npz")
    m.save(tmp)
    os.replace(tmp, path)
    return m
