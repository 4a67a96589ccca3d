from .ops import FLASH_RESIDUALS, flash_attention, flash_attention_train
from .ref import attention_ref, paged_attention_ref
from .kernel import flash_attention_fwd
from .backward import flash_attention_bwd
from .paged import paged_flash_decode

__all__ = ["FLASH_RESIDUALS", "flash_attention", "flash_attention_train",
           "attention_ref", "flash_attention_fwd", "flash_attention_bwd",
           "paged_flash_decode", "paged_attention_ref"]
