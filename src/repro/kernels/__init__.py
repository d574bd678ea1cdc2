# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.

import jax


def resolve_impl(impl: str) -> str:
    """The implementation a kernel wrapper runs: "auto" picks the
    compiled Pallas kernel on the TPU and the jnp reference elsewhere;
    "pallas" off the TPU raises instead of compiling for a chip that
    is not there ("pallas_interpret" runs the kernel body on the
    host)."""
    platform = jax.default_backend()
    if impl == "auto":
        return "pallas" if platform == "tpu" else "ref"
    if impl == "pallas" and platform != "tpu":
        raise ValueError(
            f"impl='pallas' compiles a Pallas kernel for a TPU, and "
            f"JAX's default backend is {platform!r}; ask for "
            f"impl='pallas_interpret' to run the kernel body on the host")
    return impl
