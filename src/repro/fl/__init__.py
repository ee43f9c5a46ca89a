from repro.fl.task import FLTask, make_cnn_task, make_lm_task  # noqa: F401
