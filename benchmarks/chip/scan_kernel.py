"""Where the fused scan kernel is in a trace, for the readers of
``scan_kernel_ms``, ``scan_roofline`` and ``search_other_device_ms``.

The search executable is ``jit_search_fn(...)`` on the trace's ``XLA
Modules`` line. Inside it the kernel is the Mosaic custom call
(``tpu_custom_call``) that reads the payload plane, or, where the query
batch is split into SMEM-sized chunks, the ``closed_call`` fusion around it
in the chunk loop. The plane is ``f32[n_slabs, capacity, W]`` with ``W``
the configuration's ``dim``, or ``dim`` rounded up to the 128 lanes where
the program stores the plane lane-padded; either is found. The padding
lanes are storage, not work: ``work.scan_work`` counts ``dim``.
"""
SEARCH_MODULE = r"^jit_search_fn\("
KERNEL = (r'custom_call_target="tpu_custom_call"',
          r"^%closed_call[.\d]* = .*kind=kCustom")
LANES = 128


def payload_operands(conf: dict) -> tuple[str, ...]:
    """The payload plane's operand text at ``dim`` and lane-padded."""
    d = int(conf["dim"])
    widths = dict.fromkeys((d, -(-d // LANES) * LANES))
    return tuple(f"f32[{conf['n_slabs']},{conf['capacity']},{w}]"
                 for w in widths)


def kernel_s(trace, conf: dict) -> float:
    """Device seconds of the kernel inside runs of the search executable
    (first device)."""
    return trace.ops_in_module(SEARCH_MODULE, KERNEL, payload_operands(conf))
