"""Model modules: each model's task and FLOP counts, named by a
configuration's ``model`` key."""
