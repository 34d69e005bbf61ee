"""What the two readers of the latent-attention kernels share.

A flash call with the shared second pair of operands carries ``_mla`` in its
``pl.pallas_call(name=...)``, inside the HLO instruction's name
(``checkpoint_flash_fwd_mla.7 custom-call tpu_custom_call (...)``): forward,
dkv and dq. A program without them gives ``None``, never an error.
"""
from chipbench import program_spans

#: for ``program_spans.kernel_ms_per_step`` (a regular expression); a full
#: or windowed call's name (``flash_fwd``, ``flash_bwd_dq_win``) has no
#: ``_mla`` and is not read
FLASH_MLA = r"flash_(fwd|bwd_dkv|bwd_dq)(_win)?_mla"


def kernel_ms(trace):
    """Device milliseconds a traced step spends in the ``_mla`` kernels."""
    return program_spans.kernel_ms_per_step(trace, FLASH_MLA)
