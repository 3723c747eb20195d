"""Model families: each serves every configuration that names it.

A configuration file's ``reference`` key names its family, here
``bench/families/<reference>.py``, and its plain reference,
``bench/reference/<reference>.py``.  A family module gives four functions:

* ``dims(config)``: the family's sizes, read from the configuration's own
  keys, as a frozen (hashable) object with at least ``name``, ``d_model``
  and ``vocab``;
* ``model_config(dims)``: the program's ``ModelConfig`` of that model;
* ``make_params(key_data, dims)``: the master weights drawn from the
  seed, in the program's tree layout, on the device;
* ``request_flops(dims, batch, prompt, gen)``: the operations one request
  needs, from shapes alone, never from the program.

So a model of another kind is a new family module, a new reference and
a new configuration file, with no edit to a file that exists.
"""
