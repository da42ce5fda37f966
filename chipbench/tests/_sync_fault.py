"""Child of the sync tests: run the small sync cell on four CPU devices,
sound, with one fault planted in the program's collectives, or with the
control in the program's place; print the checks and ``correct``."""
import sys

import conftest  # noqa: F401  (paths)
import _cpu


def main(fault: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.comms import api

    if fault == "no_exchange":
        # each chip keeps its own addend's block and its own shard
        def rs(x, *, axis=0, axes=None, **kw):
            n = lax.axis_size(axes)
            blk = x.shape[axis] // n
            return lax.dynamic_slice_in_dim(x, lax.axis_index(axes) * blk,
                                            blk, axis)

        def ag(x, *, axis=0, axes=None, **kw):
            return jnp.concatenate([x] * lax.axis_size(axes), axis)

        api.reduce_scatter, api.all_gather = rs, ag
    elif fault == "altered":
        gather = api.all_gather

        def ag(x, **kw):
            out = gather(x, **kw)
            return out.at[(0,) * out.ndim].add(1.0)

        api.all_gather = ag
    res = _cpu.execute(_cpu.small_cell("granite-zero1-sync"),
                       control=fault == "control")
    print(res["checks"])
    print(f"correct {res['correct']}")


if __name__ == "__main__":
    main(sys.argv[1])
