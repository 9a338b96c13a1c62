"""The language models' hot ops, each one entry point that chooses by
platform and shape between a TPU kernel (Pallas) and a plain `jax.numpy` form
that is also the kernel's reference: the attention core (blockattn.py), the
experts' grouped product and row permutations (groupmm.py, rowperm.py), the
scans (ssd.py, selscan.py), the gated delta rule (deltarule.py), the short
convolution (shortconv.py) and the passes over several residual streams
(streams.py). programs.py counts which way each call went.
"""
