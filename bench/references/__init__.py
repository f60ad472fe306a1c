"""Plain references, one module per architecture, named by a configuration
file's ``"reference"`` key.

A reference is written from the published description alone and imports
nothing of the system under test.  The harness calls four functions.
``hf`` is the file's ``hf_config``, ``glass`` its ``"glass"`` section, and
``precision`` one of ``"f32"`` (float32 at ``Precision.HIGHEST``, the
reference itself), ``"bf16"`` and ``"fp8"`` (each product's operands rounded
first; ``"fp8"`` is the control).  ``n_ffn`` is the number of layers that
hold an FFN, ``f`` its width, ``n_keep`` the blocks GLASS keeps of it.

- ``init_params(hf, key, dtype) -> params``: seeded random weights, made
  on the device when jitted; the tree, shapes and dtypes must be those the
  program's own ``model.init`` makes, since the program is handed them.
- ``prior(hf, params, corpus, bos_id, precision="f32") -> (n_ffn, f)``:
  GLASS's global prior over the int32 corpus ``(n_seqs, seq_len)``.
- ``served_logits(hf, params, prior, tokens, prompt_len, glass,
  precision="f32") -> (S, V)``: logits over one served sequence, ``tokens``
  ``(S,)`` int32, the prompt then the served tokens, zero-padded at the
  end; the prompt sets the kept blocks, which every later position runs.
  Row i scores the token at i + 1.
- ``kept_blocks(hf, params, prior, tokens, prompt_lens, glass,
  precision="f32") -> (B, n_ffn, n_keep)``: block ids each prompt keeps,
  ``tokens`` ``(B, S)`` zero-padded, ``prompt_lens`` ``(B,)``.
"""
