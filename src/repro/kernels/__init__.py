"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel is a subpackage with three modules:

- ``kernel.py`` — the ``pl.pallas_call`` body with explicit BlockSpec VMEM
  tiling, written for the TPU target (MXU-aligned block shapes, online
  accumulation in VMEM scratch that persists across the sequential grid);
- ``ops.py``    — the jit'd public wrapper (padding, layout, interpret-mode
  selection: interpret=True on non-TPU backends so CPU CI validates the
  exact kernel body the fleet runs);
- ``ref.py``    — the pure-jnp oracle every shape/dtype sweep asserts against.

Kernels:

- ``flash_attention``  — causal/local GQA attention, online softmax (prefill/train)
- ``decode_attention`` — flash-decode: one query token vs. a length-masked KV cache
- ``ssd_scan``         — Mamba-2 state-space-duality chunked scan
- ``linear_scan``      — RG-LRU gated linear recurrence (chunked, state carried in VMEM)
- ``gbrt_predict``     — GBRT ensemble inference in two-float arithmetic, rows
                         on lanes (the paper's Predictor hot loop, batched)

``dfloat`` holds the two-float arithmetic the GBRT kernel and the device
placement core share.
"""


def interpret_mode() -> bool:
    """Pallas interpret mode for this process's default device.

    Off on a TPU (Mosaic compiles the kernel), on for the CPU (tests run the
    exact kernel body through the interpreter). Any other accelerator is an
    error: these kernels are written for Mosaic TPU, and interpreting them on
    a GPU would serve from a path nobody measured.
    """
    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on a {platform!r} device; "
        "run on a TPU, or on the CPU in interpret mode")
