"""Model families: what the harness needs of a configuration's model.

A configuration names its family under ``architecture.family``, and
``spec.load_family`` imports ``families/<family>.py``. A family module
gives:

``dims(config)``              the model's sizes: an object with at least
                              ``layers`` and ``vocab``;
``program_want(dims)``        the program's ``ModelConfig`` attributes, and
                              the value each must have;
``make_params(dims, seed)``   the weights, on the device, from the seed, in
                              the program's parameter layout;
``hidden(params, seq, dims, rounding)``
``head_stats(params, h, rows, ids, dims, **control)``
                              the plain reference that ``check.compare``
                              runs: final hidden states of one sequence,
                              then the logit statistics at ``rows``
                              (``max``, ``argmax``, ``lse`` and ``at``, the
                              logit of ``ids``); ``rounding`` and the
                              controls (``check.CONTROLS``) put a stage one
                              precision lower;
``decode_flops(dims, lens)``  the flops of one decode step whose tokens see
                              ``lens`` keys;
``attention_work(dims, lens, kv_dtype)``
                              (flops, bytes) of one paged-attention launch;
``attention_launches(dims)``  paged-attention launches in one decode step.
"""
