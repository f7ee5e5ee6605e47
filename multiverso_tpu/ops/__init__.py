"""TPU data-plane kernels (Pallas) and device-side table ops."""


def pallas_interpret(devices) -> bool:
    """The ONE place ``interpret=`` is decided for every Pallas kernel in
    the package: from the platform of the devices the kernel's arrays live
    on. True exactly when those are not TPUs (tier-1 runs the kernels under
    the Pallas interpreter on CPU); never true on a TPU, where a kernel
    Mosaic refuses fails with the compiler's message instead of quietly
    running interpreted or giving way to XLA."""
    return any(d.platform != "tpu" for d in devices)


__all__ = ["pallas_interpret"]
