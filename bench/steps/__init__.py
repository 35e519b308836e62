"""Step kinds: how a cell's step is built, fed, observed and referenced.

A traffic file names its kind under ``"step"``; ``bench/steps/<kind>.py``
has ``build(config, traffic, policy)``, which returns an object with:

- ``weights_tree()`` / ``batch_tree()``: ``(shape, scale)`` trees that
  ``bench.gen`` draws from the seed;
- ``init_state(weights)``: the program's training state;
- ``program(state, batch, i) -> (state, out)``: the timed step, which the
  harness jits and compiles once;
- ``observe(states, outs)``: the observation (``bench.compare``) from host
  copies of the states before step 1, after step 1 and after step 3 and
  of the outputs of steps 1 to 3;
- ``reference(weights, batches, mode)``: the same observation from
  ``bench.reference``, at ``mode``;
- ``passes()``: ``(decision key, Conv, pass, needed)`` of every conv pass
  of one step, for the operations and bytes a step requires.
"""
