import os

# virtual 8-device CPU mesh BEFORE any jax computation, mirroring how the
# reference tests distributed semantics on local sessions (SURVEY §4).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# adaptive-tuning store isolation: the default path is the COMMITTED
# fugue_tpu/ops/_tuned.json — tests must neither dirty the repo nor
# inherit plans an earlier pytest session learned (chunk sizes would
# drift run to run). One fresh store per session; tests that exercise
# the store explicitly pass fugue.tpu.tuning.path themselves.
if "FUGUE_TPU_TUNING_PATH" not in os.environ:
    import tempfile

    os.environ["FUGUE_TPU_TUNING_PATH"] = os.path.join(
        tempfile.mkdtemp(prefix="fugue_tpu_tuning_"), "_tuned.json"
    )
