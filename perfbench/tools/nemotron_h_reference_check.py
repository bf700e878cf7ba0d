"""How ``correct`` of the Nemotron-H cell tells the architecture's mechanisms
from their absence, and the float32 state of the state-space scan from a
bfloat16 one, at the PUBLISHED widths; the readings behind
``reference/nemotron_h.py``'s limits (PERF.md §6, PR 54).  Run on the chip:

    python3 perfbench/tools/nemotron_h_reference_check.py [--seed N ...]
        [--faults NAME ...] [--fault-seeds N ...]

The comparison that decides ``correct``, through the builder's own job, with
the kernels on, for each ``--seed`` on one trainer — the walk of
``tools/qwen3_next_reference_check.py`` (its ``faults``, loaded by file name
as a module of this tool's own) over this cell and this family's faults: the
state and the decay of the scan kept in bfloat16 (the nearest precision
below the float32 the configuration states for them), the weights rounded to
bfloat16 at the start and after every update, and each switch of
``reference/nemotron_h.py``'s ``hyper`` turned.  Each fault must come out as
not agreeing, by ``LOSS_TOLERANCE``, ``GRADIENT_TOLERANCE`` or
``CHANGE_TOLERANCE``.

One JSON line a (seed, probe), one at the end.  One process: the chip
belongs to one at a time.
"""

import argparse
import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "nemotron-3-nano-30b-a3b.pretrain8192-b1-dp1"

#: the reference's switches with one mechanism left out or put in wrong
FAULTS = {
    "bf16_scan": {"scan_dtype": "bfloat16"},
    "no_softplus": {"softplus": False},
    "decay_is_one": {"decay": False},
    "no_skip": {"skip": False},
    "norm_before_the_gate": {"gate_first": False},
    "one_norm_over_all_lanes": {"norm_groups": 1},
    "head_reads_group_h_mod_g": {"head_group": "strided"},
    "convolution_without_its_bias": {"conv_bias": False},
    "softmax_router": {"router_score": "softmax"},
    "bias_in_the_weights_too": {"bias_in_weights": True},
    "no_routed_scale": {"routed_scale": 1.0},
    "no_renormalisation": {"renormalise": False},
    "plain_relu": {"activation": "relu"},
    "rotated_attention": {"rotate": True},
    "two_sub_layers_a_block": {"sublayers": 2},
}


def _walk():
    """``tools/qwen3_next_reference_check.py`` as a module of this tool's
    own, its ``FAULTS`` this family's."""
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_reference_walk",
        Path(__file__).with_name("qwen3_next_reference_check.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.FAULTS = FAULTS
    return module


def faults(cell, builder, reference, args) -> dict:
    return _walk().faults(cell, builder, reference, args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, nargs="+", default=[2147483711])
    ap.add_argument("--faults", nargs="*", default=None,
                    help="only these (`clean` is the system itself)")
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=None,
                    help="the seeds that run --faults; the others of --seed "
                         "run `clean` alone")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from perfbench import cells

    cell = cells.resolve(CELL)
    builder = cells.load_plugin("builders", cell.config["builder"])
    reference = cells.load_plugin("reference", cell.config["builder"])
    print(json.dumps(faults(cell, builder, reference, args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
