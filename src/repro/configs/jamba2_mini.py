"""jamba2-mini [hybrid] — AI21-Jamba2-Mini: blocks of 8 layers, one GQA
attention (slot 4) to seven Mamba-1 mixers, a 16-expert top-2 MoE on
every odd layer and a dense SwiGLU FFN on the others
[hf:ai21labs/AI21-Jamba2-Mini config.json].

Every width and the layout are jamba-v0.1-52b's, so the entry is that
config under its own name and source. What config.json does not say is
taken from transformers' ``JambaSparseMoeBlock`` / ``JambaMambaMixer``:
the top-2 weights are the softmax's, not renormalized, no token is
dropped, and dt, B and C pass through RMSNorm before ``dt_proj`` and
the scan. ``intermediate_size`` is read as the width of one expert and
of the dense FFN."""
import dataclasses

from repro.configs.jamba_v01_52b import CONFIG as JAMBA_V01

SOURCE = ("https://huggingface.co/ai21labs/AI21-Jamba2-Mini/blob/main/"
          "config.json")

CONFIG = dataclasses.replace(JAMBA_V01, name="jamba2-mini", source=SOURCE)
