"""A cell cut to a size a CPU test can run: a copy of the benchmark's
files in a scratch root, with the cell's configuration at the registry's
``reduced()`` widths (the keys every family shares here, the rest by its
family's ``tiny``) and its traffic and engine scaled down to match.
Only tests use it; the chip never runs these sizes."""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Tuple

from bench.spec import ROOT, load_family, load_json


def _dump(path: Path, obj) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(dest: Path, cell: str, n_layers: int = 2, attn_impl: str = "flash") -> Tuple[Path, object]:
    """(root, ArchConfig) of a scratch benchmark whose ``cell`` is tiny."""
    from repro.configs import get_arch

    dest = Path(dest)
    shutil.copytree(ROOT / "bench", dest / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    bench = load_json(dest / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:  # a cell whose files are here but not in BENCHMARK.json
        config, traffic = cell.split(".", 1)
        entry = {"name": cell, "config": config, "traffic": traffic, "chips": 1, "why": "test"}
        bench["workloads"].append(entry)
        if all(c["name"] != config for c in bench["configs"]):
            bench["configs"].append({"name": config, "file": f"bench/configs/{config}.json",
                                     "source": "", "reduced": [], "why": "test"})
        _dump(dest / "BENCHMARK.json", bench)
    cfg_path = dest / "bench" / "configs" / f"{entry['config']}.json"
    conf = load_json(cfg_path)
    a = get_arch(conf["arch"]).reduced(n_layers=n_layers)
    conf.update(num_hidden_layers=a.n_layers, hidden_size=a.d_model,
                num_attention_heads=a.n_heads, num_key_value_heads=a.n_kv_heads,
                head_dim=a.head_dim, vocab_size=a.vocab)
    conf.update(load_family(dest, conf).tiny(conf, a))
    conf["serving"]["attn_impl"] = attn_impl
    _dump(cfg_path, conf)
    tr_path = dest / "bench" / "traffic" / f"{entry['traffic']}.json"
    tr = load_json(tr_path)
    tr["prompt"].update(min=8, max=72, **({"median": 24} if "median" in tr["prompt"] else {}))
    tr["output"].update(min=4, max=12, **({"median": 8} if "median" in tr["output"] else {}))
    _dump(tr_path, tr)
    wl_path = dest / "bench" / "workloads" / f"{cell}.json"
    wl = load_json(wl_path)
    max_len = 84
    wl["engine"].update(max_slots=3, max_len=max_len,
                        kv_pool_blocks=1 + 3 * -(-max_len // conf["serving"]["kv_block_size"]))
    wl.update(lead_in_requests=4 * tr["block"], drain_s=60.0, trace={"after_s": 0.2, "seconds": 0.5})
    wl["check"].update(min_tokens=96, max_requests=16, seq_len=max_len, gen_len=12, batch=2)
    _dump(wl_path, wl)
    return dest, a
