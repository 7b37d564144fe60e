"""qwen2-7b with int8 KV cache (beyond-paper: the paper's Eq. 1 quantizer
applied to the serving cache — halves the decode memory-roofline term).
Extra config, not part of ARCH_IDS."""
from repro.configs.qwen2_7b import CONFIG as _BASE

CONFIG = _BASE.replace(name="qwen2-7b-kv8", kv_quant_bits=8)
