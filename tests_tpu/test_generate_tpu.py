"""Re-run the continuous-batching LLM serving suite on TPU: the paged
decode attention auto-selects the NATIVE Pallas ragged kernel there
(the CPU suite runs the pure-jnp gather path, plus the kernel in
interpreter mode), so allocator/scheduler/census/parity all re-verify
against the real kernel."""
import pytest

from mxnet_tpu.context import on_tpu

if not on_tpu():
    pytest.skip("TPU re-run suite needs the TPU backend",
                allow_module_level=True)

from test_generate import *   # noqa: F401,F403,E402


def test_served_decode_program_holds_the_compiled_kernel():
    """The claim above, checked once: the decode program a default server
    (``attention_impl=None``) dispatches holds one Mosaic custom call per
    layer — neither the Pallas interpreter nor the jnp gather."""
    srv = make_server().start()
    try:
        text = srv.lower_decode().as_text()
    finally:
        assert srv.drain(30)
    assert text.count("tpu_custom_call") == CFG.n_layers
