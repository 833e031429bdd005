"""Read the correctness comparison's two ends on the chip, for one cell.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, in one process, the cell runs as the benchmark runs it (a
shorter window) and the sampled requests are judged twice: the program's
served tokens, and the tokens that the control of the configuration's
model module puts first at the same positions (the dense model's: the
float32 reference with every linear layer's operands rounded to
float8).  The program's widest gaps are the lower reading of the limit
in ``bench/limits/<cell>.json``; the control's smallest is its upper
reading.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import core  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    spec = core.load_spec()
    cell, cfg_file, model = core.find_cell(spec, args.workload)
    try:
        core.require_chips(int(cell["chips"]))
    except core.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    core.enable_compile_cache()
    counter = core.CompileCounter()
    import serve_cell
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result, compared, _ = serve_cell.run(
            cell, cfg_file, model, seed, args.seconds, t0, counter,
            control=True)
        core.log("control_seed", seed=seed, correct=result["correct"],
                 program_gap=compared["max_logit_gap"]["value"],
                 control=result["control"], compared=compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
