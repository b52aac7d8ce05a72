"""repro.dist — the distributed execution substrate under the FaaS layer.

Three modules:

* ``sharding``     — logical-axis -> mesh-axis assignment (``Rules``), the
                     ``use_rules`` context, ``constrain`` annotations, and the
                     ParamSpec-tree derivations (``abstract_state`` /
                     ``param_shardings``) the dry-run and trainer consume.
* ``collectives``  — int8 wire codecs, error feedback, and the compressed
                     all-reduce used for cross-pod (DCI) gradient traffic.
* ``flash_decode`` — distributed flash decoding: LSE-merge over a
                     sequence-sharded KV cache (the ``serve_seqkv`` preset).
"""
