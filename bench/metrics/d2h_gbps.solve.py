"""Device-to-host rate of the host link, in GB/s (1e9 B): the bytes of
outputs brought back to the host (the ``host_out_bytes`` counter, all
outputs) over the time of the ``transfer`` spans that brought them, each
a ``jax.device_get`` of outputs already computed.  Nothing where no
output came back."""


def read(r):
    seconds = sum(s.duration for s in r.spans.closed("transfer"))
    moved = sum(r.spans.totals("host_out_bytes").values())
    if not seconds or not moved:
        return None
    return moved / seconds / 1e9
