"""repro — parallel-SGD SVM / MSF training framework (paper reproduction)."""
