# lint-fixture: select=kernel-name rel=stencil_tpu/ops/pack.py expect=kernel-name,kernel-name
# Seeded violations: a pallas_call with no name= at all (jax would name the
# kernel after the Python function: an anonymous custom-call in a device
# trace), and one named by a free string the kernel registry does not know.

from stencil_tpu.telemetry import names as tm


def pack_zshell_pallas(block, depth):
    from jax.experimental import pallas as pl

    def kernel(src_ref, out_ref):
        out_ref[...] = src_ref[...]

    return pl.pallas_call(
        kernel,
        grid=(depth,),
    )(block)


def unpack_zshell_pallas(block, depth):
    from jax.experimental import pallas as pl

    return pl.pallas_call(lambda s, o: None, name="my_new_kernel", grid=(depth,))(block)


def pack_yshell_pallas(block, depth):
    from jax.experimental import pallas as pl

    return pl.pallas_call(lambda s, o: None, name=tm.KERNEL_PACK_YSHELL, grid=(depth,))(block)
