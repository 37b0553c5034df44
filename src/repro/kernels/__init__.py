"""Pallas TPU kernels for the serving hot-spots, each with a pure-jnp oracle:

  flash_attention/   prefill & train attention (GQA, causal, VMEM-tiled)
  decode_attention/  paged decode attention (block-table indirection), its
                     latent-row (MLA) form, and flash-decoding
                     partial/merge primitives
  rmsnorm/           fused RMSNorm (+ residual add)
  ssd_scan/          Mamba-2 SSD chunked scan (state carried in VMEM)

The serving engine runs the compiled kernels when its device is a TPU and
the jnp references elsewhere; tests run the kernels on the CPU in Pallas
interpret mode, and ``tests/test_tpu_compile.py`` compiles them for a
described v5e. The jnp references are also the memory-bounded paths of the
GSPMD dry-run. Each ``pallas_call`` is named, so ``compiled_kernels`` can
tell which kernels a compiled TPU program calls.
"""
import collections
import re

_TPU_CALL = re.compile(r'%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*'
                       r'custom_call_target="tpu_custom_call"')


def compiled_kernels(hlo_text: str) -> collections.Counter:
    """Pallas kernel calls in a compiled TPU program's HLO text, counted by
    kernel name (a call inside a loop body counts once)."""
    return collections.Counter(_TPU_CALL.findall(hlo_text))
