"""Share of the outputs brought back to the host that ``run_chain``
wrote into host arrays an earlier call had returned, in %: the
program's ``host_out_reused_bytes`` counter over its ``host_out_bytes``
counter, all outputs.  Nothing where the program keeps no reuse counter
or no output came back."""


def read(r):
    reused = r.spans.totals("host_out_reused_bytes")
    moved = sum(r.spans.totals("host_out_bytes").values())
    if not reused or not moved:
        return None
    return 100 * sum(reused.values()) / moved
