"""Are the benchmark cells' compiled steps of two trees the same program?

A compile rehearsal for the described ``v5e:2x2`` (no chip; never a time):
"the accepted cells' steps come out as they are" is held by the optimized
HLO of each cell's step, text for text, once what only names a source
location is stripped.  One tree a process (two at once fight over libtpu's
lock), each from the root of ITS tree, so that a copy of the parent commit
(``git archive``) compiles the parent's program::

    cd <parent copy> && JAX_PLATFORMS=cpu python <repo>/benchmarks/step_hlo.py dump /root/scratch/hlo/parent
    cd <repo>        && JAX_PLATFORMS=cpu python benchmarks/step_hlo.py dump /root/scratch/hlo/change
    python benchmarks/step_hlo.py compare /root/scratch/hlo/parent /root/scratch/hlo/change

``dump OUT [cell ...]`` compiles the named cells' steps (default: every
``train_lm`` cell of the tree's ``BENCHMARK.json``) as
``tests/test_chip_compile.py::_cell_step`` builds them, 1-3 minutes a cell,
and writes ``OUT/<cell>.hlo.txt.gz``.  ``compare A B`` prints a line a cell
both hold, ``equal`` or ``DIFFER`` with the first lines that do, and exits 1
where any differs.
"""

from __future__ import annotations

import base64
import gzip
import hashlib
import json
import os
import re
import sys
from pathlib import Path


def strip(text: str) -> str:
    """``text`` (a compiled step's ``as_text()``) without what moves when a
    line above a call site is edited: the location tables at its top, each
    instruction's ``metadata={...}`` (source lines and scope names), and the
    locations inside the Mosaic kernels' serialized bodies."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    text = re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n", text,
                  count=1, flags=re.S)
    text = re.sub(r", metadata=\{[^}]*\}", "", text)

    def body(m):
        raw = base64.b64decode(m.group(1))
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        try:
            with ctx:
                asm = ir.Module.parse(raw).operation.get_asm(
                    enable_debug_info=False)
        except Exception:
            # the compiler's own grouped products: no MLIR, no path inside
            asm = raw.decode("latin1")
        return '"body":"' + hashlib.sha256(asm.encode()).hexdigest() + '"'

    return re.sub(r'"body":"([A-Za-z0-9+/=]+)"', body, text)


def dump(out: Path, cells: list) -> None:
    tree = Path.cwd()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(tree), str(tree / "tests")]
    import jax
    from jax.experimental import topologies

    import test_chip_compile

    if Path(test_chip_compile.__file__).resolve().parent.parent != tree:
        raise SystemExit(f"run from the root of the tree to compile, not "
                         f"{tree}")
    jax.config.update("jax_enable_compilation_cache", False)
    if not cells:
        manifest = json.loads((tree / "BENCHMARK.json").read_text())
        cells = [w["name"] for w in manifest["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    out.mkdir(parents=True, exist_ok=True)
    for cell in cells:
        step, _, _ = test_chip_compile._cell_step(topo, cell)
        text, mem = step.as_text(), step.memory_analysis()
        with gzip.open(out / f"{cell}.hlo.txt.gz", "wt") as f:
            f.write(text)
        print(json.dumps(dict(
            cell=cell, argument=mem.argument_size_in_bytes,
            temp=mem.temp_size_in_bytes,
            custom_calls=text.count("tpu_custom_call"))), flush=True)


def compare(a: Path, b: Path) -> int:
    differ = 0
    for fa in sorted(a.glob("*.hlo.txt.gz")):
        fb = b / fa.name
        if not fb.exists():
            continue
        la, lb = (strip(gzip.open(f, "rt").read()).splitlines()
                  for f in (fa, fb))
        cell = fa.name[:-len(".hlo.txt.gz")]
        if la == lb:
            digest = hashlib.sha256("\n".join(la).encode()).hexdigest()[:16]
            print(f"{cell} equal {len(la)} lines {digest}")
            continue
        differ += 1
        first = [i for i, (x, y) in enumerate(zip(la, lb)) if x != y][:3]
        print(f"{cell} DIFFER lines {len(la)} / {len(lb)} first {first}")
        for i in first:
            print("  A:", la[i][:300])
            print("  B:", lb[i][:300])
    return 1 if differ else 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "dump":
        dump(Path(sys.argv[2]), sys.argv[3:])
    elif len(sys.argv) == 4 and sys.argv[1] == "compare":
        sys.exit(compare(Path(sys.argv[2]), Path(sys.argv[3])))
    else:
        raise SystemExit(__doc__)
