"""Model zoo: dense GQA transformers, MoE, Mamba2, xLSTM, hybrid, modality stubs."""
from .model import (decode_step, forward, init_cache, init_params, loss_fn,
                    prefill, prefills)
