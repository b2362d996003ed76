"""The benchmark's one command.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, sets the system up through its
normal path (``TPULauncher``/``FleetScheduler`` for training, a one-replica
``ServingFleet`` for serving), warms every shape, measures for ``--seconds``,
checks the timed path's output against the float32 reference, and prints one
JSON object as the last line of standard output. It fails when JAX finds no
TPU (``ONCHIP_REHEARSAL=1`` with ``JAX_PLATFORMS=cpu`` runs the control flow
at tiny sizes and reports no device metric).

``--control 1`` (not used by the driver) is the lower-precision control of
"how correct is decided": the program with its own int8 path switched on
(``quant_training=int8`` for training, ``weight_quant=int8`` for serving); such
a run must come out not correct.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))  # the checkout: tpu_engine
sys.path.insert(0, HERE)                                     # harness, reference


def main(argv=None, traffic_update=None) -> int:
    """``traffic_update`` is for ``tools/sweep.py`` alone: numbers of the
    cell's traffic file replaced for one run that is no benchmark run."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import common, manifest, program

    program.prepare_environment()
    cell = manifest.load_cell(manifest.load_manifest(), args.workload)
    cell["traffic"].update(traffic_update or {})
    gen = manifest.load_by_name("harness/generators", cell["traffic"]["generator"])
    runner = manifest.load_by_name("harness", gen.KIND + "_runner")
    result = runner.run(cell, args, T_PROCESS_START)
    common.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
