"""The plain reference: float32 ``jax.numpy`` at ``highest`` matmul
precision, no kernels, nothing imported from the program.

``<family>.py`` holds one architecture's forward pass and loss,
``optim.py`` the optimizers as published, ``train.py`` the training
step that follows the program's first steps in blocks of rows so that
it fits beside nothing else on one chip.
"""
