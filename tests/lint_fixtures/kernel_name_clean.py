# lint-fixture: select=kernel-name rel=stencil_tpu/ops/pack.py expect=clean
# The sanctioned pattern: name= from the kernel registry, as a constant of
# telemetry/names.py (or a literal that IS a registered name).

from stencil_tpu.telemetry import names as tm


def pack_zshell_pallas(block, depth, interpret=False):
    from jax.experimental import pallas as pl

    def kernel(src_ref, out_ref):
        out_ref[...] = src_ref[...]

    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_PACK_ZSHELL,
        grid=(depth,),
        interpret=interpret,
    )(block)


def unpack_zshell_pallas(block, depth):
    from jax.experimental import pallas as pl

    return pl.pallas_call(lambda s, o: None, name="unpack_zshell", grid=(depth,))(block)
