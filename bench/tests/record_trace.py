"""Record the short ``olmoe.chat`` trace that test_bench_scopes.py reads:
a traced run of about three steps, its ``.xplane.pb`` and the compiled text
of each step program that ran in it, both gzipped. Needs a TPU:

    python bench/tests/record_trace.py <seed> <output directory>
"""
import gzip
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as R  # noqa: E402
from bench import scopes as S  # noqa: E402
from bench import trace as T  # noqa: E402

CELL, SECONDS, NAME = "olmoe.chat", 3.0, "olmoe_chat_scoped_3s"


def main(seed: int, out: Path) -> None:
    res, _, _ = R.run(ROOT, CELL, seed, SECONDS, True)
    print(json.dumps(res), flush=True)
    path = T.find_xplane(str(ROOT / ".bench_out" / "trace" /
                             f"{CELL}-{seed}-{os.getpid()}"))
    prof = S.load(path)
    from repro.serving import trace as program
    texts = program.step_program_texts()
    maps = [S.scope_map(t) for t in texts]
    keep = [texts[i] for i in sorted(set(
        S.match_programs(prof.ops, prof.modules, maps).values()))]
    out.mkdir(parents=True, exist_ok=True)
    with open(path, "rb") as f, \
            gzip.open(out / f"{NAME}.xplane.pb.gz", "wb") as g:
        shutil.copyfileobj(f, g)
    with gzip.open(out / f"{NAME}.hlo.json.gz", "wt") as g:
        json.dump(keep, g)
    print(f"kept {len(keep)} of {len(texts)} step programs", flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]), Path(sys.argv[2]))
