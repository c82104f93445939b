"""Plain references, found by name: a configuration's file names its
``reference`` and ``references/<reference>.py`` holds
``tail_logprobs(params, config, tokens, k)``: the float32 log-probabilities
[k, V] of the token that follows each of the last ``k`` positions of
``tokens``, by one straightforward forward pass over the served weights.

``plain.py`` holds the equations the families share (norm, rotary
embedding, causal attention, SwiGLU, routed experts, the decoder stack); a
family's module adds its attention and hands it to ``plain.decoder``.  A
new family is a new file here and edits nothing.
"""
