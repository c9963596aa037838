"""The benchmark's tie to the program, one module per architecture, found by
the configuration's ``architecture`` as ``chipbench/reference/`` is.

An adapter holds everything the harness knows of one architecture:

``model_config(config)``          the program's ``ModelConfig``;
``to_program_params(w, mcfg)``    the reference's weights in the program's
                                  parameter layout;
``Shapes(config)``                the counts: ``vocab``,
                                  ``kv_bytes_per_token``,
                                  ``token_flops(keys)``,
                                  ``span_flops(start, n)`` and
                                  ``weight_bytes_per_call(rows)``, the least
                                  weight bytes one jitted call reads when it
                                  computes ``rows`` rows that carry a request.
"""
