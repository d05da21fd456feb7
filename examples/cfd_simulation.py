"""End-to-end CFD driver, now on top of ``repro.flow``: a CFDlang source
file goes in, a planned memory architecture plus a pipelined execution
comes out.  The default program is the paper's full application
(``examples/cfd_pipeline.cfd``: interpolation -> gradient -> inverse
Helmholtz); point --program at any ``.cfd`` file.  The single-operator
path of earlier revisions is ``--program examples/inverse_helmholtz.cfd``.

Run:  PYTHONPATH=src python examples/cfd_simulation.py --n-eq 4096 --show-plan
"""
import argparse
import os
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402

from repro import flow  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.cfd import reference  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--program",
                    default=os.path.join(_HERE, "cfd_pipeline.cfd"),
                    help="CFDlang source file to compile and run")
    ap.add_argument("--n-eq", type=int, default=4096)
    ap.add_argument("--batch-elements", type=int, default=0,
                    help="override E (0 = let the memory planner size it)")
    ap.add_argument("--prefetch-depth", type=int, default=1,
                    help="K batches staged ahead (0 = serial baseline); "
                    "K>0 also turns on cross-batch stage pipelining")
    ap.add_argument("--serial-stages", action="store_true",
                    help="force the back-to-back stage schedule "
                    "(bitwise-equal; isolates the pipelining win)")
    ap.add_argument("--policy", default="float32")
    ap.add_argument("--backend", default="xla",
                    help="per-stage backend: xla | staged | pallas")
    ap.add_argument("--max-stages", type=int, default=None)
    ap.add_argument("--dse", action="store_true",
                    help="sweep chain design points, run the winner")
    ap.add_argument("--show-plan", action="store_true",
                    help="print the full system report before running")
    args = ap.parse_args()

    with open(args.program) as f:
        source = f.read()
    system = flow.compile(
        source,
        name=os.path.basename(args.program).removesuffix(".cfd"),
        policy=args.policy,
        backend=args.backend,
        max_stages=args.max_stages,
        batch_elements=args.batch_elements or None,
        prefetch_depth=args.prefetch_depth,
        cu_count=jax.device_count(),
        n_eq=args.n_eq,
        dse=args.dse,
    )
    if args.show_plan:
        print(system.report())
        print()
    plan = system.plan
    print(f"simulating {args.n_eq:,} elements through "
          f"{len(system.stage_names)} stages "
          f"({'->'.join(system.stage_names)}) in "
          f"{plan.batches_for(args.n_eq)} batches of "
          f"{plan.batch_elements}")
    res = system.run(
        n_eq=args.n_eq,
        pipeline_stages=False if args.serial_stages else None,
    )
    flops = res.elements * sum(
        s.program.total_flops() for s in system.chain.stages
    )
    print(f"wall: {res.wall_s:.3f}s  "
          f"({'stage-pipelined' if res.pipelined_stages else 'serial'} "
          "schedule)")
    for q, v in sorted(res.checksums.items()):
        print(f"  checksum {q} = {v:.4f}")
    print(f"GFLOPS (paper Eq. 2 accounting): "
          f"{flops / res.wall_s / 1e9 if res.wall_s else 0.0:.3f}")
    # context: the p=11 single-operator count the paper reports
    print(f"(paper flops/element at p=11: "
          f"{reference.paper_flops_per_element(11)})")


if __name__ == "__main__":
    main()
