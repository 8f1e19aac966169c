"""One module per model family the model-agnostic train runner
(``runners/train_model.py``) can run: a configuration file names its
module under ``model``."""
