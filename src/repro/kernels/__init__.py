"""Pallas kernels for the job-side models, with pure-jnp oracles in
:mod:`repro.kernels.ref` and jit'd model-layout wrappers in
:mod:`repro.kernels.ops`."""
import jax


def interpret_default() -> bool:
    """Pallas interpret mode for the backend this process runs on, asked
    at call time: the kernels compile through Mosaic on a TPU and run in
    the Pallas interpreter everywhere else."""
    return jax.default_backend() != "tpu"
